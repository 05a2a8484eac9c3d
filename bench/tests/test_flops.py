"""The operation count behind ``step_mfu``."""

import smoke

from soibench import flops
from soibench.families import dense_gqa


def test_a_decode_token_costs_twice_the_weights_it_passes_through():
    c = smoke.config("qwen3-1.7b-soi-pp")
    s = dense_gqa.sizes(c)
    d, ff, h, kv, dh, v = (s["d"], s["ff"], s["heads"], s["kv"], s["dh"],
                           s["vocab"])
    layer = d * h * dh * 2 + d * kv * dh * 2 + 3 * d * ff
    first, last = s["mid"]
    mid = last - first
    outer = s["layers"] - mid
    weights = outer * layer + (mid * layer + 2 * d * d) / 2 + 2 * d * d \
        + d * v
    attn = 4 * h * dh * (outer * 1 + mid * 1 / 2)
    assert dense_gqa.token_flops(s, 0, head=True) == 2 * weights + attn
    # attention grows with the context; a window caps it
    tok = dense_gqa.token_flops
    assert tok(s, 99, True) > tok(s, 9, True)
    w = dict(s, window=8)
    assert tok(w, 99, True) == tok(w, 15, True)


def test_prefix_cache_hits_cost_nothing():
    s = dense_gqa.sizes(smoke.config("qwen3-1.7b-soi-pp"))
    full = flops.prompt_flops(dense_gqa, s, 0, 64)
    hit = flops.prompt_flops(dense_gqa, s, 32, 64)
    assert 0 < hit < full
    assert flops.prompt_flops(dense_gqa, s, 64, 64) == 0
