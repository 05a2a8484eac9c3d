"""The plain reference computes the program's model: at smoke sizes, in
float32 on the CPU, its logits equal the program's own offline forward
pass (``repro.models.transformer.forward``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import smoke

from soibench import model, reference


@pytest.mark.parametrize("name", ["qwen3-1.7b-soi-pp", "qwen3-1.7b-soi-fp",
                                  "h2o-danube-1.8b-soi-pp"])
def test_reference_matches_program_forward(name):
    from repro.models import transformer as T
    c = smoke.config(name)
    cfg = model.program_config(c)
    w = model.make_weights(c, model.seed_key(3, 0))
    s = model.sizes(c)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2048,), 0,
                              s["vocab"])
    want = T.forward(w, cfg, toks[None])[0]
    head = w["embed"].T if s["tied"] else w["lm_head"]
    got = jnp.einsum("sd,dv->sv", reference.hidden(w, s, toks), head,
                     precision="highest")
    assert float(jnp.max(jnp.abs(got - want))) < 1e-3 * float(
        jnp.max(jnp.abs(want)))


def test_weights_have_the_program_layout():
    from repro.distributed.sharding import split_axes
    from repro.models import transformer as T
    c = smoke.config("qwen3-1.7b-soi-pp")
    cfg = model.program_config(c)
    want = jax.eval_shape(lambda k: split_axes(T.init(k, cfg))[0],
                          jax.random.PRNGKey(0))
    got = model.weight_shapes(c)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)


def test_weights_follow_the_seed_and_are_bfloat16_values():
    c = smoke.config("qwen3-1.7b-soi-pp")
    a = model.make_weights(c, model.seed_key(2 ** 31 + 11, 0))
    b = model.make_weights(c, model.seed_key(2 ** 31 + 11, 0))
    d = model.make_weights(c, model.seed_key(2 ** 31 + 12, 0))
    assert all(jax.tree.leaves(jax.tree.map(
        lambda x, y: bool(jnp.array_equal(x, y)), a, b)))
    assert not np.array_equal(a["embed"], d["embed"])
    e = a["embed"]
    assert jnp.array_equal(e, e.astype(jnp.bfloat16).astype(jnp.float32))


def test_gaps_are_zero_for_the_reference_own_tokens():
    c = smoke.config("qwen3-1.7b-soi-pp")
    s = model.sizes(c)
    w = model.make_weights(c, model.seed_key(5, 0))
    n = reference.padded_len(40, s["stride"])
    toks = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, s["vocab"])
    h = reference.hidden(w, s, toks)
    best = jnp.argmax(jnp.einsum("sd,dv->sv", h, w["embed"].T,
                                 precision="highest"), axis=-1)
    g = reference.gaps(w, reference.frozen(s), toks, best.astype(jnp.int32))
    assert float(jnp.max(g)) == 0.0
    g2 = reference.gaps(w, reference.frozen(s), toks,
                        (best + 1).astype(jnp.int32) % s["vocab"])
    assert float(jnp.min(g2)) > 0.0
