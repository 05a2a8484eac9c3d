"""repro.analysis: the hot-path contract checker catches known violations
and passes the real engine.

Two halves:
  * seeded-violation fixtures — an undonated big carry, a donation XLA must
    drop, a hidden per-step ``.item()``, a weak-type carry, a bf16
    narrowing step — each must be FLAGGED with its stable code (a checker
    that cannot fail its fixtures guards nothing);
  * ``test_hotpath_contracts`` — the shipped engine configurations (dense/
    paged x GQA/MLA x speculate on/off) must produce ZERO findings. This is
    the same gate CI runs via ``python -m repro.analysis --ci``.
"""


import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import donation, dtype_drift, hostsync, retrace
from repro.analysis.report import (Finding, Report, compare_to_baseline,
                                   load_baseline)
from repro.engine.contracts import (CheckedJit, DroppedDonationError,
                                    JitEntry, checked_jit, host_get,
                                    sanctioned_drain)


def _codes(findings):
    return {f.code for f in findings}


# ---------------------------------------------------------------- fixtures

def test_undonated_big_buffer_flagged():
    """A large carried buffer passed without donation (and without a
    readonly_ok justification) is DON001."""
    big = jnp.zeros((256, 256), jnp.float32)   # 256KB >> BIG_BYTES

    def step(state, x):
        return state + x, state.sum()

    entry = JitEntry("leaky_step", checked_jit(step), (big, 1.0),
                     donate=(), state_args=(0,))
    findings = donation.check_entry("fixture", entry)
    assert "DON001" in _codes(findings)


def test_dropped_donation_flagged():
    """Donating a buffer no output can alias (no output of its element
    count: jax also donates to a same-size output of another dtype) is
    dropped by XLA: DON002 from the lowering trap, DroppedDonationError
    from the executing wrapper."""
    x = jnp.zeros((128, 128), jnp.float32)

    def drop(v):
        return (v * 2).astype(jnp.bfloat16)[0]

    # jax emits the dropped-donation warning once per lowering, so give the
    # static check and the executing wrapper each a fresh program
    entry = JitEntry("drop_step", checked_jit(drop, donate_argnums=(0,)),
                     (x,), donate=(0,), state_args=(0,))
    findings = donation.check_entry("fixture", entry)
    assert "DON002" in _codes(findings)
    with pytest.raises(DroppedDonationError):
        checked_jit(drop, donate_argnums=(0,))(
            jnp.zeros((128, 129), jnp.float32))


def test_good_donation_not_flagged():
    x = jnp.zeros((128, 128), jnp.float32)
    jfn = checked_jit(lambda v: v + 1, donate_argnums=(0,))
    entry = JitEntry("clean_step", jfn, (x,), donate=(0,), state_args=(0,))
    assert donation.check_entry("fixture", entry) == []
    jfn(jnp.zeros((128, 128), jnp.float32))   # and it executes warning-free


def test_hidden_item_in_step_loop_flagged():
    src = """
import numpy as np

def serve(engine, params, state, n):
    outs = []
    for _ in range(n):
        state, res = engine.generate(params, state)
        outs.append(res.data.item())
    return outs
"""
    findings = hostsync.scan_source(src, "fixture.py")
    assert "SYNC001" in _codes(findings)


def test_same_iteration_drain_flagged():
    src = """
def serve(engine, params, state, n):
    for _ in range(n):
        state, res = engine.generate(params, state)
        res = res.convert_to_numpy()
    return state
"""
    findings = hostsync.scan_source(src, "fixture.py")
    assert "SYNC003" in _codes(findings)


def test_deferred_drain_and_pragma_not_flagged():
    src = """
import numpy as np

def serve(engine, params, state, n):
    pending = None
    for _ in range(n):
        state, res = engine.generate(params, state)
        if pending is not None:
            host = pending.convert_to_numpy()
            tok = int(host.get_result_at_slot(0).tokens[0])
        debug = np.asarray(res.logits)  # sync-ok: debugging fixture
        pending = res
    return state
"""
    assert hostsync.scan_source(src, "fixture.py") == []


def test_jit_bound_loop_detected():
    """Loops over a local name bound to jax.jit(...) count as step loops."""
    src = """
import jax

def bench(params, state, tok, n):
    jstep = jax.jit(lambda p, s, t: (s, t))
    for _ in range(n):
        state, out = jstep(params, state, tok)
        tok = out.item()
    return tok
"""
    findings = hostsync.scan_source(src, "fixture.py")
    assert "SYNC001" in _codes(findings)


def test_scalar_arg_retrace_flagged():
    """A Python int in a traced position traces weak-typed: RET002
    statically; and alternating scalar/array inputs at one call site
    genuinely compiles two programs (the failure RET002 predicts)."""
    jfn = checked_jit(lambda x, off: x + off)
    x = jnp.zeros((4,), jnp.float32)
    entry = JitEntry("offset_step", jfn, (x, 3), donate=(), state_args=())
    findings = retrace._static_scan("fixture", entry)
    assert "RET002" in _codes(findings)
    jfn(x, 1), jfn(x, 2)
    scalar_only = jfn._cache_size()   # values share ONE weak-typed trace
    jfn(x, jnp.asarray(2, jnp.int32))
    assert jfn._cache_size() == scalar_only + 1


def test_weak_type_carry_flagged():
    """A Python scalar reaching the carry flips it weak-typed: DT003 (and
    the next call retraces — the failure RET/DT jointly guard against)."""
    def step(state):
        # clock leaf replaced by a bare Python scalar -> weak f32 carry
        return {"x": state["x"] + 1, "t": 1.0}

    st = {"x": jnp.zeros((8,), jnp.float32),
          "t": jnp.zeros((), jnp.float32)}   # strong f32 in
    entry = JitEntry("weak_step", checked_jit(step), (st,),
                     donate=(0,), state_args=(0,), carry=(0, None))
    findings = dtype_drift._check_carry("fixture", entry)
    assert "DT003" in _codes(findings)


def test_bf16_narrowing_flagged():
    def step(state):
        return (state.astype(jnp.bfloat16) @ jnp.eye(8, dtype=jnp.bfloat16)
                ).astype(jnp.float32)

    x = jnp.zeros((8, 8), jnp.float32)
    entry = JitEntry("narrow_step", checked_jit(step), (x,),
                     donate=(0,), state_args=(0,))
    findings = dtype_drift._walk_program(
        "fixture", entry, np.dtype(np.float32).itemsize)
    assert "DT002" in _codes(findings)


def test_carry_dtype_drift_flagged():
    def step(state):
        return state.astype(jnp.bfloat16)

    x = jnp.zeros((8,), jnp.float32)
    entry = JitEntry("drift_step", checked_jit(step), (x,),
                     donate=(0,), state_args=(0,), carry=(0, None))
    findings = dtype_drift._check_carry("fixture", entry)
    assert "DT001" in _codes(findings)


def test_sanctioned_drain_nests_and_restores():
    from repro.engine import contracts
    assert not contracts.in_sanctioned_drain()
    with sanctioned_drain():
        assert contracts.in_sanctioned_drain()
        with sanctioned_drain():
            assert contracts.in_sanctioned_drain()
        assert contracts.in_sanctioned_drain()
    assert not contracts.in_sanctioned_drain()
    out = host_get({"a": jnp.arange(3)})
    assert isinstance(out["a"], np.ndarray)


def test_checked_jit_passthrough():
    jfn = checked_jit(lambda x: x + 1)
    assert isinstance(jfn, CheckedJit)
    jfn.lower(jnp.zeros((2,)))          # pjit attrs reachable
    assert jfn._cache_size() >= 0


def test_baseline_protocol(tmp_path):
    report = Report(findings=[
        Finding("donation", "DON001", "t:gen", "msg"),
        Finding("retrace", "RET001", "t:ins", "msg")])
    base = tmp_path / "base.json"
    # empty/missing baseline: everything is new
    diff = compare_to_baseline(report, str(base))
    assert not diff.clean and len(diff.new) == 2
    # accept one finding; the other stays new, plus one stale entry
    report_accept = Report(findings=[
        report.findings[0],
        Finding("dtype", "DT001", "gone:entry", "msg")])
    report_accept.write(str(base))
    assert len(load_baseline(str(base))) == 2
    diff = compare_to_baseline(report, str(base))
    assert [f.code for f in diff.new] == ["RET001"]
    assert [f.code for f in diff.accepted] == ["DON001"]
    assert diff.stale == [("dtype", "DT001", "gone:entry")]


# ------------------------------------------------------- the real contract

HOTPATH_TARGETS = ["gqa-dense", "gqa-paged", "gqa-dense-spec",
                   "gqa-paged-spec", "mla-dense", "mla-paged",
                   "mla-dense-spec", "mla-paged-spec"]


@pytest.mark.parametrize("name", HOTPATH_TARGETS)
def test_hotpath_contracts(name):
    """The shipped engine configurations carry zero contract findings:
    donation wired and never dropped, no per-step host sync, O(1) compiled
    programs under repeat traffic, dtype-stable carry."""
    from repro.analysis import analyze
    report = analyze([name])
    assert report.findings == [], report.render()


def test_repo_host_code_clean():
    """The static host-sync pass over the repo's own driver code (serving
    loop, sessions, engine, benchmarks) is clean."""
    findings = hostsync.run_files()
    assert findings == [], "\n".join(f.render() for f in findings)
