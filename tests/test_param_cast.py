"""SOIEngine casts a tree of f32 weights to the compute dtype once.

The claims under test:
  * one tree served through every entry (decode state, prefill, generate)
    is cast once, on dense, paged-and-chunked and speculative engines;
  * a second tree replaces the first's copy, and the engine keeps neither
    the first tree nor its copy alive;
  * a float32 config, or a tree with no f32 leaf, passes through uncopied;
  * the cast tree holds the SOI groups' weights split apart;
  * what is served is what the programs gave when they cast the f32 tree
    themselves: the same tokens, and logits to rtol 1e-6.
"""

import dataclasses
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.sharding import split_axes
from repro.engine import SOIEngine
from repro.models import transformer as T


def _cfg(dtype="bfloat16"):
    import repro.configs.qwen3_1_7b as Q
    return dataclasses.replace(Q.smoke_config(soi="pp"), dtype=dtype)


def _params(cfg, key=0):
    params, _ = split_axes(T.init(jax.random.PRNGKey(key), cfg))
    return params


ENGINES = {
    "dense": {},
    "paged_chunked": {"paged": True, "page_size": 8, "prefill_chunk": 8,
                      "prefix_cache": True},
    "speculative": {"paged": True, "page_size": 8, "speculate": 2},
}


def _engine(cfg, kind="paged_chunked"):
    return SOIEngine(cfg, max_concurrent_decodes=3, max_len=64,
                     **ENGINES[kind])


def _serve(eng, params, steps):
    """Two requests at both SOI phases, one inserted mid-decode; returns
    every step's (tokens, logits) on the host."""
    prompt = np.arange(3, 40, dtype=np.int32)
    ds = eng.init_decode_state(params)
    ds = eng.insert(eng.prefill(params, prompt[:13]), ds, 0)
    out = []
    for i in range(steps):
        if i == 3:
            ds = eng.insert(eng.prefill(params, prompt[:10]), ds, 1)
        ds, res = eng.generate(params, ds)
        res = res.convert_to_numpy()
        out.append((np.asarray(res.data), np.asarray(res.logits)))
    return out


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_one_tree_is_cast_once(kind):
    cfg = _cfg()
    params = _params(cfg)
    eng = _engine(cfg, kind)
    _serve(eng, params, 6)
    assert eng.param_casts == 1
    cast = jax.tree.leaves(eng._compute_params(params))
    assert {p.dtype for p in cast} == {jnp.dtype(jnp.bfloat16)}
    assert eng.param_casts == 1


def test_a_second_tree_replaces_the_first():
    cfg = _cfg()
    eng = _engine(cfg)
    first = _params(cfg, 0)
    _serve(eng, first, 2)
    src = weakref.ref(jax.tree.leaves(first)[0])
    old = weakref.ref(jax.tree.leaves(eng._compute_params(first))[0])
    second = _params(cfg, 1)
    _serve(eng, second, 2)
    assert eng.param_casts == 2
    gc.collect()
    assert old() is None                  # the first copy was released
    del first
    gc.collect()
    assert src() is None                  # and the engine kept no source
    _serve(eng, second, 1)
    assert eng.param_casts == 2


def test_no_copy_without_f32_leaves_to_cast():
    cfg = _cfg("float32")
    params = _params(cfg)
    eng = _engine(cfg)
    _serve(eng, params, 2)
    assert eng.param_casts == 0
    assert eng._compute_params(params) is params
    bf = _cfg()
    eng = _engine(bf)
    cast = jax.tree.map(lambda p: p.astype(jnp.bfloat16), _params(bf))
    assert eng._compute_params(cast) is cast
    assert eng.param_casts == 0


def test_shapes_map_to_the_cast_tree_structs():
    cfg = _cfg()
    eng = _engine(cfg)
    params = _params(cfg)
    out = eng._compute_params(jax.eval_shape(lambda: params))
    assert eng.param_casts == 0
    cast = eng._compute_params(params)
    assert jax.tree.structure(out) == jax.tree.structure(cast)
    assert [(p.shape, p.dtype) for p in jax.tree.leaves(out)] == \
        [(p.shape, p.dtype) for p in jax.tree.leaves(cast)]


def test_soi_segments_are_split_once():
    """The cast tree holds the SOI groups' weights apart, so no program
    slices the stacked layers on every call; a plain config's tree keeps
    its stacked segments."""
    cfg = _cfg()
    params = _params(cfg)
    cast = _engine(cfg)._compute_params(params)
    pre, mid, post = T._split_segment_params(params["segments"], cfg)
    assert set(cast["segments"]) == {"pre", "mid", "post"}
    for got, want in ((cast["segments"]["pre"], pre),
                      (cast["segments"]["mid"], mid),
                      (cast["segments"]["post"], post)):
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(g, w.astype(jnp.bfloat16))
    plain = dataclasses.replace(cfg, soi=None)
    cast = _engine(plain)._compute_params(_params(plain))
    assert isinstance(cast["segments"], list)


def test_served_tokens_match_programs_that_cast_themselves():
    """The reference engine hands the f32 tree straight to its jitted
    programs (``_gen``, ``_prefill_chunk``, ...), which cast it inside, as
    every call did before the engine kept a cast copy."""
    cfg = _cfg()
    params = _params(cfg)
    got = _serve(_engine(cfg), params, 10)
    ref_eng = _engine(cfg)
    ref_eng._compute_params = lambda p: p
    want = _serve(ref_eng, params, 10)
    assert ref_eng.param_casts == 0
    for (d, lg), (d_ref, lg_ref) in zip(got, want):
        np.testing.assert_array_equal(d, d_ref)
        np.testing.assert_allclose(lg, lg_ref, rtol=1e-6)
