"""The program's own spans and scopes, from trace events to per-layer
numbers."""

import glob
import pathlib

import jax
import jax.numpy as jnp

from soibench import inside


MS = 1e6       # trace times are ns


def _gen(step, mid, start, dur, dispatch_at):
    return [["engine.generate", start * MS, dur * MS,
             {"step": step, "mid": mid, "active": 4, "pages": 0, "cow": 0}],
            ["engine.dispatch", dispatch_at * MS, 0.5 * MS,
             {"program": "gen"}]]


def _inside():
    # three steps; the device runs each step after the last (times in ms):
    # step 0 dispatched at 10, runs 20..120 (mid fires); step 1 dispatched
    # at 30, runs 120..180; step 2 dispatched at 130, runs 180..240
    spans = (_gen(0, 1, 0, 20, 10) + _gen(1, 0, 25, 10, 30)
             + _gen(2, 1, 125, 10, 130))
    ops = [["convert_element_type.1", 20, 30, "cast_params"],
           ["fusion.2", 50, 40, "soi_middle"],
           ["fusion.3", 90, 30, None],
           ["convert_element_type.1", 120, 30, "cast_params"],
           ["fusion.3", 150, 30, None],
           ["convert_element_type.1", 180, 30, "cast_params"],
           ["fusion.4", 210, 30, "lm_head"],
           ["convert_element_type.5", 250, 10, "cast_params"]]
    programs = [["jit__gen(7)", 20, 100], ["jit__gen(7)", 120, 60],
                ["jit__gen(7)", 180, 60], ["jit__prefill_chunk(8)", 250, 20]]
    return inside.Inside(
        spans=spans, bench=[],
        programs=[[n, s * MS, d * MS] for n, s, d in programs],
        ops=[[n, s * MS, d * MS, sc] for n, s, d, sc in ops])


def _ms(pairs):
    return [(a["step"], (s / MS, e / MS)) for a, (s, e) in pairs]


def test_each_step_pairs_with_the_execution_after_its_dispatch():
    ins = _inside()
    assert _ms(inside.steps(ins, 0, 1000 * MS)) == [
        (0, (20, 120)), (1, (120, 180)), (2, (180, 240))]
    # the window keeps the steps begun in it, with their execution
    assert [a["step"] for a, _ in inside.steps(ins, 25 * MS, 1000 * MS)] \
        == [1, 2]
    assert inside.step_ms(ins, 0, 1000 * MS, 1) == (100 + 60) / 2
    assert inside.step_ms(ins, 0, 1000 * MS, 0) == 60
    assert inside.host_ms(ins, 0, 1000 * MS) == (20 + 10 + 10) / 3


def test_an_execution_may_appear_just_before_its_dispatch():
    """The device clock of a trace can run a millisecond or so ahead of
    the host's: a step the device ran as soon as the host dispatched it
    still pairs with it, not with the step before."""
    ins = _inside()
    ins.spans[5][1] = 181 * MS              # step 2 dispatched at 181 ms
    ins.spans[4][1] = 176 * MS
    assert _ms(inside.steps(ins, 0, 1000 * MS)) == [
        (0, (20, 120)), (1, (120, 180)), (2, (180, 240))]


def test_steps_at_the_edges_of_the_trace_stay_unpaired():
    ins = _inside()
    del ins.programs[2]                     # step 2 ran after the trace
    assert _ms(inside.steps(ins, 0, 1000 * MS)) == [
        (0, (20, 120)), (1, (120, 180))]
    ins = _inside()
    ins.spans = ins.spans[2:]               # step 0 began before it
    assert _ms(inside.steps(ins, 0, 1000 * MS)) == [
        (1, (120, 180)), (2, (180, 240))]


def test_scope_time_per_execution():
    ins = _inside()
    end = 1000 * MS
    total, busy, n = inside.scope_ns(ins, "jit__gen", "cast_params", 0, end)
    assert (total / MS, busy / MS, n) == (90, 220, 3)
    assert inside.scope_ms(ins, 0, end, "jit__gen", "cast_params") == 30
    assert inside.scope_ms(ins, 0, end, "jit__prefill_chunk",
                           "cast_params") == 10
    assert inside.scope_ns(ins, "jit__gen", None, 0, end)[0] == 60 * MS
    # a program without scopes (one from before they were added)
    for op in ins.ops:
        op[3] = None
    assert inside.scope_ns(ins, "jit__gen", "cast_params", 0, end) is None
    assert inside.scope_ms(ins, 0, end, "jit__gen", "cast_params") is None


def test_a_trace_without_engine_spans_reads_nothing():
    ins = _inside()
    ins.spans = []
    assert inside.steps(ins, 0, 1000 * MS) == []
    assert inside.step_ms(ins, 0, 1000 * MS, 1) is None
    assert inside.host_ms(ins, 0, 1000 * MS) is None


def test_scope_of_takes_the_innermost_scope():
    assert inside.scope_of("jit(_gen)/cast_params/convert_element_type") == \
        "cast_params"
    assert inside.scope_of("jit(_gen)/cond/branch_1_fun/soi_middle/while/"
                           "body/dot_general") == "soi_middle"
    assert inside.scope_of("jit(_gen)/concatenate") is None
    assert inside.scope_of(None) is None and inside.scope_of("") is None


def test_op_names_from_the_hlo_a_trace_keeps(tmp_path):
    """The profiler keeps each compiled module's HLO in the trace's
    metadata plane; the op names in it carry the named scopes."""
    @jax.jit
    def f(w, x):
        with jax.named_scope("cast_params"):
            wb = w.astype(jnp.bfloat16)

        @jax.named_scope("soi_middle")
        def mid(x):
            return jnp.sin(x @ wb)
        return jax.lax.cond(x.sum() > 0, mid, lambda x: x, x)

    w, x = jnp.ones((32, 32)), jnp.ones((4, 32), jnp.bfloat16)
    f(w, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(w, x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    names = inside.hlo_op_names(pathlib.Path(path).read_bytes())
    (module, table), = [(m, t) for m, t in names.items()
                        if m.startswith("jit_f(")]
    scopes = {inside.scope_of(v) for v in table.values()}
    assert {"cast_params", "soi_middle"} <= scopes
    assert any(v.endswith("cast_params/convert_element_type")
               for v in table.values())


def test_readers_on_a_recorded_chip_trace():
    """Three generate steps of the chat cell and the admission between
    them (a prefill of three chunks and an insert), recorded on one TPU
    v5e (``data/engine_trace.json``). The step after the admission ran as
    soon as it was dispatched, and the trace shows it starting a
    millisecond before its ``engine.dispatch``."""
    import json
    d = json.loads((pathlib.Path(__file__).parent / "data"
                    / "engine_trace.json").read_text())
    ins = inside.Inside.from_json(d)
    lo, hi = d["window"]
    pairs = inside.steps(ins, lo, hi)
    assert [(a["step"], a["mid"]) for a, _ in pairs] == [(20, 0), (21, 1),
                                                         (22, 0)]
    assert [run for _, run in pairs] == [
        tuple(r) for r in inside.executions(ins, "jit__gen", lo, hi)]
    dispatch = [s[1] for s in ins.spans if s[0] == "engine.dispatch"
                and s[3]["program"] == "gen"]
    assert pairs[2][1][0] < dispatch[2]
    fired = inside.step_ms(ins, lo, hi, 1)
    skipped = inside.step_ms(ins, lo, hi, 0)
    assert 80 < fired < 86 and 60 < skipped < 64
    cast = inside.scope_ms(ins, lo, hi, "jit__gen", "cast_params")
    assert 12.6 < cast < 21                 # the cast's bytes at 819 GB/s
    chunk_cast = inside.scope_ms(ins, lo, hi, "jit__prefill_chunk",
                                 "cast_params")
    assert 12.6 < chunk_cast < 16
    assert 0.1 < inside.host_ms(ins, lo, hi) < 3
    scoped = {o[3] for o in ins.ops}
    assert set(inside.SCOPES) | {None} == scoped
    assert all(o[0].startswith(("convert_element_type", "copy", "fusion",
                                "bitcast"))
               for o in ins.ops if o[3] == "cast_params")
