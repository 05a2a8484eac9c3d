"""The ``cost`` pass: parser goldens, trip-count fixtures, branch-mode
analysis, the closed-form middle-trunk floor, and the COST certifiers.

Three layers, mirroring how the pass can fail:

  * parser goldens — closed-form programs (a dense matmul, a GQA attention
    block) where the FLOP count is hand-computable, plus hand-written HLO
    exercising trip-count extraction for nested ``while`` loops whose
    bound is CARRIED in the loop tuple (the regression the old
    max-constant heuristic silently under-counted as trip 1);
  * certifier fixtures — synthetic cell costs that MUST trip each COST
    code (a certifier that cannot fail its fixtures guards nothing);
  * the property over the live matrix — every SOI cell's compiled step
    really is cheaper off-phase than phase-0, by at least the middle
    trunk's closed-form matmul floor.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import cost
from repro.analysis.hlo import analyze, flops_of
from repro.analysis.targets import MATRIX, get_target


# ------------------------------------------------------------ parser goldens

def test_matmul_flops_golden():
    """A single dense matmul is exactly 2*m*n*k FLOPs."""
    m, k, n = 8, 16, 32
    f = flops_of(lambda a, b: a @ b, jnp.zeros((m, k)), jnp.zeros((k, n)))
    assert f == 2 * m * n * k


def test_gqa_attention_block_flops_golden():
    """One GQA attention block (q/k/v/o projections + scores + values) in
    explicit einsums: every contraction is hand-computable, and the parser
    must count exactly their sum."""
    B, S, d, H, KV, hd = 2, 8, 32, 4, 2, 16
    g = H // KV

    def block(x, ctx, wq, wk, wv, wo):
        q = jnp.einsum("bd,dhk->bhk", x, wq)           # 2*B*d*H*hd
        k = jnp.einsum("bsd,dvk->bsvk", ctx, wk)       # 2*B*S*d*KV*hd
        v = jnp.einsum("bsd,dvk->bsvk", ctx, wv)       # 2*B*S*d*KV*hd
        qg = q.reshape(B, KV, g, hd)
        s = jnp.einsum("bvgk,bsvk->bvgs", qg, k)       # 2*B*H*S*hd
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bvgs,bsvk->bvgk", p, v)        # 2*B*H*S*hd
        return jnp.einsum("bhk,hkd->bd", o.reshape(B, H, hd), wo)  # 2*B*H*hd*d

    args = (jnp.zeros((B, d)), jnp.zeros((B, S, d)),
            jnp.zeros((d, H, hd)), jnp.zeros((d, KV, hd)),
            jnp.zeros((d, KV, hd)), jnp.zeros((H, hd, d)))
    expected = (2 * B * d * H * hd                  # q
                + 2 * 2 * B * S * d * KV * hd       # k, v
                + 2 * 2 * B * H * S * hd            # scores, values
                + 2 * B * H * hd * d)               # o
    assert flops_of(block, *args) == expected


def test_live_nested_scan_trip_counts():
    """A scan-inside-a-scan through the real jax lowering: 5 x 3 x one
    8x8x8 matmul — both with XLA's known_trip_count annotation and with it
    stripped (forcing the condition-extraction fallback)."""
    import re

    def nested(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ ci, None
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    txt = jax.jit(nested).lower(jnp.eye(8)).compile().as_text()
    expected = 5 * 3 * 2 * 8 * 8 * 8
    assert analyze(txt)["flops"] == expected
    stripped = re.sub(r'"known_trip_count":\{"n":"\d+"\},?', "", txt)
    assert analyze(stripped)["flops"] == expected


# Hand-written HLO: outer loop's bound is a constant in its condition, but
# the INNER loop's bound travels in the carried tuple (loop-invariant code
# motion hoists it out of the condition) — the shape the old max-constant
# heuristic read as trip 1. 5 outer x 3 inner x 1024-FLOP dot = 15360.
NESTED_CARRIED_BOUND_HLO = """\
HloModule nested_fixture

%inner_body (p: (s32[], s32[], f32[8,8])) -> (s32[], s32[], f32[8,8]) {
  %p = (s32[], s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element((s32[], s32[], f32[8,8]) %p), index=0
  %n = s32[] get-tuple-element((s32[], s32[], f32[8,8]) %p), index=1
  %x = f32[8,8] get-tuple-element((s32[], s32[], f32[8,8]) %p), index=2
  %one = s32[] constant(1)
  %ip = s32[] add(s32[] %i, s32[] %one)
  %y = f32[8,8] dot(f32[8,8] %x, f32[8,8] %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], s32[], f32[8,8]) tuple(s32[] %ip, s32[] %n, f32[8,8] %y)
}

%inner_cond (p: (s32[], s32[], f32[8,8])) -> pred[] {
  %p = (s32[], s32[], f32[8,8]) parameter(0)
  %i2 = s32[] get-tuple-element((s32[], s32[], f32[8,8]) %p), index=0
  %n2 = s32[] get-tuple-element((s32[], s32[], f32[8,8]) %p), index=1
  ROOT %lt = pred[] compare(s32[] %i2, s32[] %n2), direction=LT
}

%outer_body (q: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %q = (s32[], f32[8,8]) parameter(0)
  %j = s32[] get-tuple-element((s32[], f32[8,8]) %q), index=0
  %x0 = f32[8,8] get-tuple-element((s32[], f32[8,8]) %q), index=1
  %zero = s32[] constant(0)
  %three = s32[] constant(3)
  %init = (s32[], s32[], f32[8,8]) tuple(s32[] %zero, s32[] %three, f32[8,8] %x0)
  %w = (s32[], s32[], f32[8,8]) while((s32[], s32[], f32[8,8]) %init), condition=%inner_cond, body=%inner_body
  %xn = f32[8,8] get-tuple-element((s32[], s32[], f32[8,8]) %w), index=2
  %one2 = s32[] constant(1)
  %jp = s32[] add(s32[] %j, s32[] %one2)
  ROOT %t2 = (s32[], f32[8,8]) tuple(s32[] %jp, f32[8,8] %xn)
}

%outer_cond (q: (s32[], f32[8,8])) -> pred[] {
  %q = (s32[], f32[8,8]) parameter(0)
  %j2 = s32[] get-tuple-element((s32[], f32[8,8]) %q), index=0
  %five = s32[] constant(5)
  ROOT %lt2 = pred[] compare(s32[] %j2, s32[] %five), direction=LT
}

ENTRY %main (a: f32[8,8]) -> (s32[], f32[8,8]) {
  %a = f32[8,8] parameter(0)
  %z = s32[] constant(0)
  %init2 = (s32[], f32[8,8]) tuple(s32[] %z, f32[8,8] %a)
  ROOT %wo = (s32[], f32[8,8]) while((s32[], f32[8,8]) %init2), condition=%outer_cond, body=%outer_body
}
"""


def test_nested_while_carried_bound_regression():
    """The inner condition holds NO constant — its bound must be resolved
    through the while's init tuple in the parent computation."""
    assert analyze(NESTED_CARRIED_BOUND_HLO)["flops"] == 5 * 3 * 2 * 8 ** 3


def test_cond_branch_selection_modes():
    """``cond="max"`` charges a conditional's expensive branch, ``"min"``
    the cheap one — the mechanism that separates phase-0 from off-phase
    without phase-specialized lowerings."""
    def f(p, x):
        return jax.lax.cond(p, lambda v: v @ v, lambda v: v + 1.0, x)

    txt = (jax.jit(f).lower(jnp.asarray(True), jnp.zeros((16, 16)))
           .compile().as_text())
    assert analyze(txt, cond="max")["flops"] == 2 * 16 ** 3
    assert analyze(txt, cond="min")["flops"] == 0
    with pytest.raises(ValueError):
        analyze(txt, cond="typo")


# ------------------------------------------------------- certifier fixtures

def _ec(flops, flops_min, nbytes, peak=0.0, contract=None):
    return cost.EntryCost(flops=flops, flops_min=flops_min, bytes=nbytes,
                          bytes_min=nbytes, peak_bytes=peak,
                          contract=contract)


def _gqa_soi_cfg():
    import dataclasses

    import repro.configs.qwen3_1_7b as Q
    return dataclasses.replace(Q.smoke_config(soi="pp"), dtype="float32")


def test_cost001_lost_skip_flagged():
    """A generate step whose off-phase branch saves LESS than the middle
    trunk's matmul floor means the SOI skip was lost in lowering."""
    cfg = _gqa_soi_cfg()
    floor = cost.middle_trunk_floor(cfg, 2)
    assert floor > 0
    ct = {"role": "generate", "stride": 2, "batch": 2}
    bad = {"generate": _ec(1e6, 1e6 - floor / 2, 1e6, contract=ct)}
    good = {"generate": _ec(1e6, 1e6 - floor * 1.5, 1e6, contract=ct)}
    assert {f.code for f in cost._certify_cell("x", bad, cfg)} == {"COST001"}
    assert cost._certify_cell("x", good, cfg) == []


def test_cost002_paged_byte_blowup_flagged():
    ct = {"role": "generate", "stride": 1, "batch": 2}
    cells = {
        "gqa-dense": {"generate": _ec(1e6, 1e6, 1e6, contract=ct)},
        "gqa-paged": {"generate": _ec(1e6, 1e6, 8e6, contract=ct)},
    }
    found = cost._certify_cross(cells)
    assert {f.code for f in found} == {"COST002"}
    cells["gqa-paged"]["generate"] = _ec(1e6, 1e6, 1.1e6, contract=ct)
    assert cost._certify_cross(cells) == []


def test_cost003_spec_window_identity_flagged():
    """The fused K-token window must not exceed (K-1) off-phase drafts +
    K worst-case verify steps of the non-speculative sibling."""
    g = {"role": "generate", "stride": 2, "batch": 2}
    w = {"role": "spec_window", "stride": 2, "k": 2, "batch": 2}
    cells = {
        "gqa-dense": {"generate": _ec(10.0, 6.0, 1e6, contract=g)},
        # bound = (2-1)*6 + 2*10 = 26; 40 is a re-computing window
        "gqa-dense-spec": {"speculative_window":
                           _ec(40.0, 20.0, 1e6, contract=w)},
    }
    assert ({f.code for f in cost._certify_cross(cells)} == {"COST003"})
    cells["gqa-dense-spec"]["speculative_window"] = \
        _ec(26.0, 18.0, 1e6, contract=w)
    assert cost._certify_cross(cells) == []


def test_cost004_recomputing_hydrate_flagged():
    cfg = _gqa_soi_cfg()
    ct = {"role": "hydrate", "tokens": 16, "stride": 2}
    chunk = _ec(6e6, 6e6, 4e6,
                contract={"role": "prefill_chunk", "tokens": 16, "batch": 1,
                          "stride": 2})
    bad = {"hydrate": _ec(5e5, 5e5, 5e6, contract=ct),
           "prefill_chunk": chunk}
    codes = [f.code for f in cost._certify_cell("pc", bad, cfg)]
    assert codes.count("COST004") == 2        # recompute AND O(prompt) bytes
    good = {"hydrate": _ec(0.0, 0.0, 7e4, contract=ct),
            "prefill_chunk": chunk}
    assert cost._certify_cell("pc", good, cfg) == []


def test_cost005_baseline_drift_flagged():
    base = {"tolerance": 0.10,
            "cells": {"gqa-dense": {"generate":
                                    {"flops": 100.0, "flops_min": 50.0,
                                     "bytes": 100.0, "bytes_min": 50.0,
                                     "peak_bytes": 100.0}}}}
    ok = {"gqa-dense": {"generate":
                        {"flops": 105.0, "flops_min": 50.0, "bytes": 100.0,
                         "bytes_min": 50.0, "peak_bytes": 100.0}}}
    assert cost._certify_baseline(ok, base) == []
    grown = {"gqa-dense": {"generate":
                           {"flops": 120.0, "flops_min": 50.0,
                            "bytes": 100.0, "bytes_min": 50.0,
                            "peak_bytes": 100.0}}}
    assert ({f.code for f in cost._certify_baseline(grown, base)}
            == {"COST005"})
    missing = {"gqa-dense": {"new_entry":
                             {"flops": 1.0, "flops_min": 1.0, "bytes": 1.0,
                              "bytes_min": 1.0, "peak_bytes": 1.0}}}
    assert ({f.code for f in cost._certify_baseline(missing, base)}
            == {"COST005"})


# ------------------------------------------- the property on the live matrix

@pytest.mark.parametrize("name", [n for n in MATRIX])
def test_offphase_cheaper_than_phase0(name):
    """For EVERY matrix cell: the compiled decode step's off-phase branch
    contains fewer FLOPs than phase-0, by at least the middle trunk's
    closed-form matmul floor (x K for fused speculative windows). This is
    the paper's complexity claim as a property of the optimized HLO."""
    target = get_target(name)
    costs = cost.measure_target(target)
    ename = ("speculative_window" if "speculative_window" in costs
             else "generate")
    c = costs[ename]
    ct = c.contract
    assert ct is not None and ct["role"] in ("generate", "spec_window")
    mult = ct.get("k", 1) if ct["role"] == "spec_window" else 1
    floor = cost.middle_trunk_floor(target.cfg, ct["batch"]) * mult
    assert floor > 0
    assert c.flops_min < c.flops
    assert c.flops - c.flops_min >= floor, (
        f"{name}.{ename}: gap {c.flops - c.flops_min:,.0f} below middle "
        f"floor {floor:,.0f}")


# Hand-written HLO: a Pallas chunk-attention kernel after TPU lowering is
# ONE opaque custom-call — no dots for the parser to count. Pricing goes
# through the repro.kernels.costs registry, keyed on the pallas_call name
# carried in the op metadata. Shapes: q (2,16,4,16), k/v (2,48,2,16).
KERNEL_CC_HLO = """\
HloModule kernel_cc_fixture

ENTRY %main (q: f32[2,16,4,16], k: f32[2,48,2,16], v: f32[2,48,2,16], qp: s32[2,16], kp: s32[2,48]) -> f32[2,16,4,16] {
  %q = f32[2,16,4,16] parameter(0)
  %k = f32[2,48,2,16] parameter(1)
  %v = f32[2,48,2,16] parameter(2)
  %qp = s32[2,16] parameter(3)
  %kp = s32[2,48] parameter(4)
  ROOT %cc = f32[2,16,4,16] custom-call(f32[2,16,4,16] %q, f32[2,48,2,16] %k, f32[2,48,2,16] %v, s32[2,16] %qp, s32[2,48] %kp), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/pallas_call[name=chunk_attention]"}
}
"""


def test_kernel_custom_call_priced():
    """A registered kernel custom-call is charged its closed-form cost —
    the same 4*q_elems*Sk the reference attention would be billed."""
    got = analyze(KERNEL_CC_HLO)
    q_elems = 2 * 16 * 4 * 16
    assert got["flops"] == 4.0 * q_elems * 48
    io = 2 * (q_elems * 4) + 2 * (2 * 48 * 2 * 16 * 4) \
        + 2 * 16 * 4 + 2 * 48 * 4
    assert got["bytes"] == io
    assert got["unpriced_custom_calls"] == []


@pytest.mark.parametrize("name", ["decode_attention", "paged_decode_attention",
                                  "chunk_attention", "mla_chunk_attention"])
def test_kernel_name_longest_match(name):
    """Compiled TPU HLO carries the kernel name only inside ``op_name``; a
    name holding another registered one (paged_decode_attention holds
    decode_attention) resolves to the longer, so it is priced by its own
    formula."""
    from repro.analysis.hlo import kernel_name_in
    assert kernel_name_in(
        f'metadata={{op_name="jit(step)/{name}/pallas_call"}}') == name
    assert kernel_name_in('op_name="jit(step)/mystery/pallas_call"') is None


def test_kernel_custom_call_unpriced_reported():
    """A Pallas-target custom-call with an unknown name lands in
    unpriced_custom_calls; non-kernel targets (Sharding etc.) stay exempt."""
    txt = KERNEL_CC_HLO.replace("name=chunk_attention", "name=mystery_fuse")
    got = analyze(txt)
    assert got["flops"] == 0
    assert got["unpriced_custom_calls"] == ["mystery_fuse"]
    with pytest.raises(ValueError, match="mystery_fuse"):
        cost._require_priced("cell.generate", got)
    benign = txt.replace('custom_call_target="tpu_custom_call"',
                         'custom_call_target="Sharding"')
    assert analyze(benign)["unpriced_custom_calls"] == []


def test_kernel_cost_registry_matches_hlo_convention():
    """Registry formulas follow the parser's 2*out*contracted dot pricing:
    the stmc_conv kernel's closed form equals the flops the parser counts
    for the equivalent plain dot."""
    from repro.kernels import costs as kcosts

    def sh(dtype, *dims):
        per = {"f32": 4, "s32": 4, "bf16": 2}[dtype]
        elems = 1
        for d in dims:
            elems *= d
        return kcosts.Shape(dtype, tuple(dims), elems * per)

    out = kcosts.price("stmc_conv", sh("f32", 8, 32),
                       [sh("f32", 8, 96), sh("f32", 96, 32)])
    assert out["flops"] == flops_of(
        lambda a, b: a @ b, jnp.zeros((8, 96)), jnp.zeros((96, 32)))
