"""SOIEngine's own instrumentation: host spans, compile counter, scopes.

The claims under test (docs/OBSERVABILITY.md lists the spans):
  * the engine's host work emits ``engine.*`` spans, nested in their
    callers and carrying their args, to the in-memory recorder and as
    ``jax.profiler`` annotations;
  * the ``mid`` arg of ``engine.generate``, predicted on the host, equals
    the device telemetry's ``mid_fired`` on every step;
  * ``compiles`` counts one trace per program, and an ``engine.dispatch``
    that traced anew says so (``traced=1``);
  * the compiled generate program puts the weight cast under the
    ``cast_params`` scope and the cond's true branch under ``soi_middle``;
  * served, the weights are cast once, under an ``engine.cast_params``
    span, and the programs the engine dispatches hold no cast.
"""

import dataclasses
import glob
import re

import jax
import numpy as np
import pytest

from repro.distributed.sharding import split_axes
from repro.engine import SOIEngine
from repro.models import transformer as T
from repro.obs import record_spans


def _cfg(mode="pp", dtype="float32"):
    import repro.configs.qwen3_1_7b as Q
    return dataclasses.replace(Q.smoke_config(soi=mode), dtype=dtype)


def _params(cfg):
    params, _ = split_axes(T.init(jax.random.PRNGKey(0), cfg))
    return params


def _paged(cfg, **kw):
    return SOIEngine(cfg, max_concurrent_decodes=3, max_len=64, paged=True,
                     page_size=8, prefill_chunk=8, prefix_cache=True,
                     telemetry=True, **kw)


def _serve(eng, params, plan, steps):
    """Serve on ``eng``: ``plan`` maps a step index to the (slot, prompt)
    inserted before it; every result is drained one step late, as the
    serving loop does. Returns the drained telemetry vectors."""
    ds = eng.init_decode_state(params)
    pending, out = None, []
    for i in range(steps):
        for slot, prompt in plan.get(i, ()):
            ds = eng.insert(eng.prefill(params, prompt), ds, slot)
        ds, res = eng.generate(params, ds)
        if pending is not None:
            out.append(pending.convert_to_numpy().metrics)
        pending = res
    out.append(pending.convert_to_numpy().metrics)
    return ds, out


def test_spans_nest_in_their_callers_with_args():
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(cfg)
    prompt = np.arange(1, 40, dtype=np.int32)
    with record_spans() as rec:
        # the second prompt shares the first's leading 32 tokens: a
        # prefix-cache hit that hydrates
        ds, _ = _serve(eng, params, {0: [(0, prompt[:35])],
                                     2: [(1, prompt[:33])]}, 4)
        ds = eng.free_slot(ds, 0)
    parent = {id(r): rec.records[r.parent] if r.parent is not None else None
              for r in rec.records}

    def under(name):
        return {parent[id(r)].name if parent[id(r)] else None
                for r in rec.named(name)}

    gens = rec.named("engine.generate")
    assert [g.args["step"] for g in gens] == [0, 1, 2, 3]
    assert [g.args["active"] for g in gens] == [1, 1, 2, 2]
    for g in gens:
        assert set(g.args) == {"step", "mid", "active", "pages", "cow"}
        kids = [c.name for c in rec.children(g)]
        assert kids[:2] == ["engine.back_pages", "engine.flush_cow"]
        assert kids[-2:] == ["engine.refresh_page_maps", "engine.dispatch"]
        assert g.start <= min(c.start for c in rec.children(g))
        assert max(c.end for c in rec.children(g)) <= g.end
    assert under("engine.back_pages") == {"engine.generate"}
    assert under("engine.refresh_page_maps") == {"engine.generate"}
    assert rec.named("engine.refresh_page_maps")[0].args["maps"] == 2

    pre = rec.named("engine.prefill")
    assert [p.args["tokens"] for p in pre] == [35, 33]
    assert [p.args["hit"] for p in pre] == [0, 32]
    assert [p.args["chunks"] for p in pre] == [5, 1]
    assert under("engine.prefix_lookup") == {"engine.prefill"}
    assert under("engine.hydrate") == {"engine.prefill"}
    assert under("engine.prefill_chunk") == {"engine.prefill"}
    assert under("engine.snapshot") == {"engine.prefill"}
    assert [c.args["index"] for c in rec.named("engine.prefill_chunk")] == \
        [0, 1, 2, 3, 4, 4]

    assert [i.args["slot"] for i in rec.named("engine.insert")] == [0, 1]
    assert under("engine.free_slot") == {None}
    assert under("engine.drain") == {None}
    assert len(rec.named("engine.drain")) == 4
    dispatch = {d.args["program"]: parent[id(d)].name
                for d in rec.named("engine.dispatch")}
    assert dispatch["gen"] == "engine.generate"
    assert dispatch["ins"] == "engine.insert"
    assert dispatch["prefill_chunk"] == "engine.prefill_chunk"
    assert dispatch["hydrate"] == "engine.hydrate"
    assert dispatch["fresh_prefix"] == "engine.prefill"
    assert dispatch["release"] == "engine.free_slot"


def test_an_insert_into_an_occupied_slot_frees_it_inside_the_insert():
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(cfg)
    prompt = np.arange(1, 40, dtype=np.int32)
    ds = eng.init_decode_state(params)
    ds = eng.insert(eng.prefill(params, prompt[:9]), ds, 0)
    with record_spans() as rec:
        ds = eng.insert(eng.prefill(params, prompt[:11]), ds, 0)
    ins = rec.named("engine.insert")[0]
    kids = [c.name for c in rec.children(ins)]
    assert kids == ["engine.free_slot", "engine.dispatch"]


def test_spans_are_profiler_annotations_with_their_args(tmp_path):
    from jax.profiler import ProfileData
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(cfg)
    ds = eng.init_decode_state(params)
    ds = eng.insert(eng.prefill(params, np.arange(1, 12)), ds, 0)
    ds, res = eng.generate(params, ds)          # compile outside the trace
    res.convert_to_numpy()
    jax.profiler.start_trace(str(tmp_path))
    ds, res = eng.generate(params, ds)
    res.convert_to_numpy()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns, dict(e.stats)))
    (g0, gd, gen), = events["engine.generate"]
    assert gen == {"step": 1, "mid": 1, "active": 1, "pages": 0, "cow": 0}
    (d0, dd, disp), = events["engine.dispatch"]
    assert disp == {"program": "gen"}
    assert g0 <= d0 and d0 + dd <= g0 + gd
    for name in ("engine.back_pages", "engine.flush_cow",
                 "engine.refresh_page_maps", "engine.drain"):
        assert name in events


@pytest.mark.parametrize("layout", ["paged", "dense"])
def test_host_mid_matches_device_mid_fired(layout):
    """Inserts at prompt lengths of both parities, at steps of both
    parities, so the batch is phase-misaligned: the middle fires on some
    steps and not others, and the host says which on every one."""
    cfg = _cfg()
    params = _params(cfg)
    if layout == "paged":
        eng = _paged(cfg)
    else:
        eng = SOIEngine(cfg, max_concurrent_decodes=3, max_len=64,
                        telemetry=True)
    prompt = np.arange(1, 40, dtype=np.int32)
    with record_spans() as rec:
        ds, mets = _serve(eng, params, {0: [(0, prompt[:9])],
                                        3: [(1, prompt[:12])],
                                        6: [(2, prompt[:7])]}, 12)
        ds = eng.free_slot(ds, 1)
        ds, res = eng.generate(params, ds)
        mets.append(res.convert_to_numpy().metrics)
    host = [g.args["mid"] for g in rec.named("engine.generate")]
    device = [int(m[-2]) for m in mets]
    assert host == device
    assert 0 < sum(device) < len(device)
    assert [g.args["active"] for g in rec.named("engine.generate")] == \
        [int(m[-1]) for m in mets]


def test_compiles_counts_one_trace_per_program_and_marks_a_retrace():
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(cfg)
    prompt = np.arange(1, 40, dtype=np.int32)
    ds, _ = _serve(eng, params, {0: [(0, prompt[:35])],
                                 2: [(1, prompt[:33]), (2, prompt[:20])]}, 6)
    ds = eng.free_slot(ds, 2)
    used = {"gen", "ins", "prefill_chunk", "fresh_prefix", "hydrate",
            "release"}
    assert {p: n for p, n in eng.compiles.items() if n} == \
        dict.fromkeys(used, 1)
    assert eng.prefill_compiles == 1 and eng.hydrate_compiles == 1
    with record_spans() as rec:
        ds, res = eng.generate(params, ds)
        jax.clear_caches()                  # the next call traces anew
        ds, res = eng.generate(params, ds)
        ds, res = eng.generate(params, ds)
    res.convert_to_numpy()
    traced = [d.args.get("traced", 0) for d in rec.named("engine.dispatch")
              if d.args["program"] == "gen"]
    assert traced == [0, 1, 0]
    assert eng.compiles["gen"] == 2


def _loc_names(mlir: str) -> dict:
    return dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', mlir, re.M))


def test_compiled_generate_scopes_the_cast_and_the_middle():
    cfg = _cfg(dtype="bfloat16")
    params = _params(cfg)
    eng = SOIEngine(cfg, max_concurrent_decodes=3, max_len=64, paged=True,
                    page_size=8, prefill_chunk=8)
    ds = eng.init_decode_state(params)
    lowered = eng._gen.lower(params, ds)
    mlir = lowered.as_text(debug_info=True)
    locs = _loc_names(mlir)
    casts = re.findall(r"stablehlo\.convert %arg\d+ : \(tensor<[^>]*xf32>\) "
                       r"-> tensor<[^>]*xbf16> loc\((#loc\d+)\)", mlir)
    n_f32 = sum(1 for p in jax.tree.leaves(params) if p.dtype == np.float32)
    assert len(casts) == n_f32 > 0
    assert all("/cast_params/" in locs[loc] for loc in casts)

    hlo = lowered.compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    assert "jit(_gen)/cast_params/convert_element_type" in op_names
    true_branch = [n for n in op_names if "/cond/branch_1_fun/" in n]
    assert true_branch
    assert all("/soi_middle/" in n for n in true_branch)
    assert any("soi_middle" in n and n.endswith("dot_general")
               for n in true_branch)
    for scope in ("soi_pre", "soi_post", "lm_head"):
        assert any(f"/{scope}/" in n for n in op_names), scope


def test_served_programs_carry_no_cast():
    """The engine casts the weights once, under an ``engine.cast_params``
    span, and the generate and prefill_chunk programs it dispatches take
    the cast tree: no op of theirs is under the ``cast_params`` scope."""
    cfg = _cfg(dtype="bfloat16")
    params = _params(cfg)
    eng = _paged(cfg)
    with record_spans() as rec:
        ds, _ = _serve(eng, params, {0: [(0, np.arange(1, 20))]}, 3)
    (cast,) = rec.named("engine.cast_params")
    n_f32 = sum(p.size for p in jax.tree.leaves(params)
                if p.dtype == np.float32)
    assert cast.args == {"bytes": 2 * n_f32}
    assert eng.param_casts == 1
    served = eng._compute_params(params)
    ms = jax.eval_shape(eng._fresh_prefix_fn, served)
    tok = np.zeros((1, eng.prefill_chunk), np.int32)
    n = np.int32(eng.prefill_chunk)
    for fn, args in ((eng._gen, (served, ds)),
                     (eng._prefill_chunk_fn, (served, ms, tok, np.int32(0),
                                              n))):
        hlo = fn.lower(*args).compile().as_text()
        op_names = re.findall(r'op_name="([^"]*)"', hlo)
        assert op_names
        assert not [o for o in op_names if "/cast_params/" in o]
