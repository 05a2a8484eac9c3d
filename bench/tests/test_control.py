"""The control: the reference in float8 put in the program's place reads
far above the program, on the same prompts and served tokens."""

import smoke
import test_harness

from soibench import check, families, model


def test_float8_control_reads_above_the_program():
    from soibench import cell_run
    seen = {}
    real = check.compare

    def compare(fam, weights, sizes, reqs, max_len):
        found = real(fam, weights, sizes, reqs, max_len)
        seen["program"] = found["max_gap"]
        seen["control"] = check.control(fam, weights, sizes, reqs, max_len)
        return found

    check.compare = compare
    cell_run.check.compare = compare
    try:
        out, _ = test_harness.run("qwen3-chat-closed", seconds=3.0)
    finally:
        check.compare = real
        cell_run.check.compare = real
    assert out["correct"] is True
    assert seen["control"] > test_harness.SMOKE_LIMIT > seen["program"]


def test_control_of_the_reference_itself_is_nought():
    """The control compares against the float32 reference: its own
    argmax reads 0 (what the float8 reading adds is its rounding)."""
    import jax
    import jax.numpy as jnp
    from soibench import reference
    c = smoke.config("qwen3-1.7b-soi-pp")
    fam = families.of(c)
    s = fam.sizes(c)
    w = model.make_weights(c, model.seed_key(9, 0))
    n = reference.padded_len(8, s["stride"])
    toks = jax.random.randint(jax.random.PRNGKey(4), (n,), 0, s["vocab"])
    g = reference.control_gaps(fam, w, reference.frozen(s), toks, quant=None)
    assert float(jnp.max(g)) == 0.0
