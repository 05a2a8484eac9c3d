"""Sharding rules unit tests + a small real-mesh integration test (runs in a
subprocess with 8 forced host devices so the main process keeps 1 CPU)."""

import subprocess
import sys
import textwrap

import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES
from repro.distributed.sharding import ShardingRules, spec_for


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)
        self.empty = False


def test_spec_basic_tp():
    rules = ShardingRules(data_axes=("data",))
    mesh = _FakeMesh({"data": 16, "model": 16})
    s = spec_for(("embed", "heads", "head_dim"), (2048, 16, 128), rules, mesh)
    assert s == P(None, "model", None)


def test_spec_divisibility_fallback():
    rules = ShardingRules(data_axes=("data",))
    mesh = _FakeMesh({"data": 16, "model": 16})
    notes = []
    s = spec_for(("embed", "kv_heads", "head_dim"), (2048, 8, 128), rules,
                 mesh, notes)
    assert s == P(None, None, None)          # 8 kv heads can't split 16 ways
    assert notes


def test_spec_fsdp_and_axis_conflict():
    rules = ShardingRules(data_axes=("pod", "data"), fsdp=True)
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    s = spec_for(("embed", "ff"), (4096, 16384), rules, mesh)
    assert s == P(("pod", "data"), "model")
    # first-come-first-served: two logical names mapping to "model"
    rules2 = ShardingRules(data_axes=("data",), seq_shard=True)
    s2 = spec_for(("batch", "seq_act", "heads"), (256, 4096, 16), rules2,
                  _FakeMesh({"data": 16, "model": 16}))
    assert s2 == P("data", "model", None)


def test_shapes_table_complete():
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                           "long_500k"}
    assert SHAPES["train_4k"]["global_batch"] == 256
    assert SHAPES["long_500k"]["seq_len"] == 524288


@pytest.mark.slow
def test_sharded_train_step_runs_on_mesh():
    """Integration: real 8-device mesh, jit with shardings, one numeric step
    (subprocess so the forced device count doesn't leak into other tests)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        import repro.configs as C
        from repro.launch import specs as S
        from repro.launch.steps import make_train_step
        from repro.distributed.sharding import ShardingRules, split_axes
        from repro.models import transformer as T
        from repro.optim import adamw_init

        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             devices=jax.devices()[:8],
                             axis_types=(AxisType.Auto,) * 2)
        rules = ShardingRules(data_axes=("data",))
        cfg = C.get_smoke("qwen3-1.7b")
        pshapes, psh = S.param_shardings(cfg, rules, mesh)
        params, _ = split_axes(T.init(jax.random.PRNGKey(0), cfg))
        params = jax.device_put(params, psh)
        opt = jax.device_put(adamw_init(params),
                             {"mu": psh, "nu": psh,
                              "count": NamedSharding(mesh, P())})
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                    cfg.vocab)
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
        bsh = {k: NamedSharding(mesh, P("data", None)) for k in batch}
        batch = jax.device_put(batch, bsh)
        step = jax.jit(make_train_step(cfg, rules, mesh, microbatches=2),
                       in_shardings=(psh, {"mu": psh, "nu": psh,
                                           "count": NamedSharding(mesh, P())},
                                     bsh))
        p2, o2, m = step(params, opt, batch)
        l0 = float(m["loss"])
        p3, o3, m2 = step(p2, o2, batch)
        assert float(m2["loss"]) < l0, (l0, float(m2["loss"]))
        print("OK", l0, float(m2["loss"]))
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={**__import__('os').environ,
                            "PYTHONPATH": "src"})
    assert "OK" in r.stdout, r.stdout + r.stderr
