"""The reduction from trace events to per-layer numbers."""

from soibench import costs, profile

PEAK = {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9}


# an "XLA Ops" event name from a chip trace of the chat cell's generate
# program (TPU v5 lite), as the profiler gives it
PAGED = ("%paged_decode_attention.24 = bf16[16,8,2,128]{3,2,1,0:T(2,128)"
         "(2,1)S(1)} custom-call(s32[16,128]{1,0:T(8,128)S(1)} "
         "%get-tuple-element.2001, s32[16]{0:T(128)S(1)} "
         "%get-tuple-element.2000, bf16[16,8,2,128]{3,2,1,0:T(2,128)(2,1)"
         "S(1)} %copy.192, bf16[2049,16,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
         "%fusion.470, bf16[2049,16,8,128]{3,2,1,0:T(8,128)(2,1)} "
         "%fusion.472, s32[2049,1,16]{2,1,0:T(1,128)S(1)} %copy.197), "
         'custom_call_target="tpu_custom_call", operand_layout_constraints='
         "{s32[16,128]{1,0}, s32[16]{0}, bf16[16,8,2,128]{3,2,1,0}, "
         "bf16[2049,16,8,128]{3,2,1,0}, bf16[2049,16,8,128]{3,2,1,0}, "
         "s32[2049,1,16]{2,1,0}}, frontend_attributes={kernel_metadata={}}")


def test_kernel_names_and_shapes_come_from_the_hlo_text():
    name, kernel, shapes, container = profile.parse_op(PAGED)
    assert (name, kernel, container) == ("paged_decode_attention.24",
                                         "paged_decode_attention", False)
    assert profile.parse_op("%fusion.3 = f32[2]{0} fusion(f32[2]{0} "
                            "%paged_decode_attention.24)")[1] is None
    out, ops = profile.hlo_shapes(PAGED)
    assert out.dims == (16, 8, 2, 128) and out.itemsize == 2
    assert [o.dims for o in ops] == [(16, 128), (16,), (16, 8, 2, 128),
                                     (2049, 16, 8, 128), (2049, 16, 8, 128),
                                     (2049, 1, 16)]
    cost = costs.paged_decode_attention(out, ops)
    # 16 slots x 128 pages of 16 tokens, k and v of 8 x 128 bf16 per token
    assert cost["flops"] == 4.0 * 16 * 8 * 2 * 128 * 128 * 16
    assert cost["bytes"] > 16 * 128 * 16 * 8 * 128 * 2 * 2


def _events():
    # two programs, four ops, one idle gap inside a host span (times in ns)
    return profile.Events(
        ops=[["while.1", 0, 150, None, None, True],
             ["fusion.1", 0, 100, None, None, False],
             ["paged_decode_attention.2", 100, 50, "paged_decode_attention",
              [[[1, 1, 1, 8], 2], [[[1, 1], 4], [[1], 4], [[1, 1, 1, 8], 2],
                                    [[2, 16, 1, 8], 2], [[2, 16, 1, 8], 2],
                                    [[2, 1, 16], 4]]], False],
             ["fusion.3", 400, 100, None, None, False],
             ["fusion.4", 500, 100, None, None, False]],
        programs=[["jit__gen(1)", 0, 150], ["jit__prefill_chunk(2)", 400,
                                            200]],
        spans=[["generate", 0, 20], ["drain", 160, 200]])


def test_reduce_counts_busy_programs_kernels_and_gaps():
    s = profile.reduce(_events(), 0, 1000, [(0, 700)], PEAK)
    assert s.window_s == 1e-6 and s.busy_s == 350e-9
    # in flight 0..700 ns, busy 350 of it
    assert abs(s.idle_share - 0.5) < 1e-12
    assert s.programs == {"jit__gen": (1, 150e-9),
                          "jit__prefill_chunk": (1, 200e-9)}
    calls, secs, least = s.kernels["paged_decode_attention"]
    assert calls == 1 and secs == 50e-9 and 0 < least < secs
    assert s.breakdown["idle_gaps"] == [["drain", 250e-9]]
    assert s.breakdown["device_ops"][0] == ["jit__gen:fusion.1", 100e-9]
    assert ["jit__prefill_chunk:fusion.3", 100e-9] in \
        s.breakdown["device_ops"]


def test_a_kernel_call_without_shapes_has_no_roofline():
    ev = _events()
    ev.ops[2][4] = None
    s = profile.reduce(ev, 0, 1000, [(0, 700)], PEAK)
    assert s.kernels["paged_decode_attention"][2] is None


def test_clock_offset_matches_spans_in_order():
    ev = _events()
    host = [("generate", 10.0, 10.1), ("drain", 10.00000016, 10.2)]
    off = profile.clock_offset(ev, host, 10.0)
    assert abs(off - (-10.0)) < 1e-6


def test_reduce_a_recorded_chip_trace():
    """Two generate steps of the chat cell, recorded on one TPU v5e
    (``data/chat_trace.json``: the ops of an off-phase step, of the
    phase-0 step after it, and of one prefill chunk)."""
    import json
    import pathlib
    ev = profile.Events.from_json(json.loads(
        (pathlib.Path(__file__).parent / "data" / "chat_trace.json")
        .read_text()))
    gens = [p for p in ev.programs if p[0].startswith("jit__gen")]
    off, on = next((a, b) for a, b in zip(gens, gens[1:])
                   if any(a[1] <= o[1] < a[1] + a[2] for o in ev.ops))
    lo, hi = int(off[1]), int(on[1] + on[2])
    s = profile.reduce(ev, lo, hi, [(lo, hi)], PEAK)
    calls, secs, least = s.kernels["paged_decode_attention"]
    # 14 outer layers on both steps, the 14 middle layers on phase 0 only
    assert calls == 14 + 28 and on[2] > off[2]
    assert 0.10 < least / secs < 0.15          # about 12% of its roofline
    assert s.programs["jit__gen"][0] == 2
    assert s.idle_share < 0.01 and s.busy_s <= s.window_s
    assert s.breakdown["device_ops"][0][0] == \
        "jit__gen:paged_decode_attention"
