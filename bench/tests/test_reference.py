"""The plain reference computes the program's model: at smoke sizes, in
float32 on the CPU, its logits equal the program's own offline forward
pass (``repro.models.transformer.forward``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import smoke

from soibench import families, model, reference


@pytest.mark.parametrize("name", ["qwen3-1.7b-soi-pp", "qwen3-1.7b-soi-fp",
                                  "h2o-danube-1.8b-soi-pp"])
def test_reference_matches_program_forward(name):
    from repro.models import transformer as T
    c = smoke.config(name)
    fam = families.of(c)
    cfg = fam.program_config(c)
    w = model.make_weights(c, model.seed_key(3, 0))
    s = fam.sizes(c)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2048,), 0,
                              s["vocab"])
    want = T.forward(w, cfg, toks[None])[0]
    got = jnp.einsum("sd,dv->sv", fam.hidden(w, s, toks), fam.head(w, s),
                     precision="highest")
    assert float(jnp.max(jnp.abs(got - want))) < 1e-3 * float(
        jnp.max(jnp.abs(want)))


def test_weights_have_the_program_layout():
    from repro.distributed.sharding import split_axes
    from repro.models import transformer as T
    c = smoke.config("qwen3-1.7b-soi-pp")
    cfg = families.of(c).program_config(c)
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
    fam = families.of(c)
    s = fam.sizes(c)
    w = model.make_weights(c, model.seed_key(5, 0))
    n = reference.padded_len(40, s["stride"])
    toks = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, s["vocab"])
    h = fam.hidden(w, s, toks)
    best = jnp.argmax(jnp.einsum("sd,dv->sv", h, w["embed"].T,
                                 precision="highest"), axis=-1)
    g = reference.gaps(fam, w, reference.frozen(s), toks,
                       best.astype(jnp.int32))
    assert float(jnp.max(g)) == 0.0
    g2 = reference.gaps(fam, w, reference.frozen(s), toks,
                        (best + 1).astype(jnp.int32) % s["vocab"])
    assert float(jnp.min(g2)) > 0.0


def test_bfloat16_params_hold_the_same_values():
    """``param_dtype: bfloat16`` holds the same bfloat16 values in a
    bfloat16 tree, at half the bytes; the reference upcasts them and reads
    the same gaps as from the float32 tree."""
    c = smoke.config("qwen3-1.7b-soi-pp")
    b = dict(c, param_dtype="bfloat16")
    key = model.seed_key(2 ** 31 + 11, 0)
    w32, w16 = model.make_weights(c, key), model.make_weights(b, key)
    assert jax.tree.structure(w16) == jax.tree.structure(w32)
    assert {x.dtype for x in jax.tree.leaves(w16)} == {jnp.dtype("bfloat16")}
    assert all(jax.tree.leaves(jax.tree.map(
        lambda x, y: bool(jnp.array_equal(x.astype(jnp.float32), y)),
        w16, w32)))
    assert 2 * model.param_bytes(b) == model.param_bytes(c)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), model.weight_shapes(b)) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), w16)
    fam = families.of(c)
    s = fam.sizes(c)
    n = reference.padded_len(40, s["stride"])
    toks = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, s["vocab"])
    targets = jax.random.randint(jax.random.PRNGKey(3), (n,), 0, s["vocab"])
    g32, g16 = (reference.gaps(fam, w, reference.frozen(s), toks, targets)
                for w in (w32, w16))
    np.testing.assert_array_equal(np.asarray(g16), np.asarray(g32))
