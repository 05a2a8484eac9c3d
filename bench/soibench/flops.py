"""Operations the SOI model's schedule requires, from the configuration's
shapes, for the ``step_mfu`` metrics.

Only what the schedule requires counts, not what the program happens to
execute (a batch-wide middle that fires for one slot, a per-step cast of
the weights, padded chunk rows):

* a token computed by the model costs 2 x the weights it passes through:
  every outer layer, the compress conv and the middle's layers once per
  ``stride`` tokens, the fuse, and the LM head where a logit is needed
  (each decode token and the last prompt token);
* attention adds 4 x context x heads x head_dim per layer, the context
  capped at the window; the middle attends over frames.
"""

from __future__ import annotations


def _layer_weights(s: dict) -> int:
    d, h, kv, dh, ff = s["d"], s["heads"], s["kv"], s["dh"], s["ff"]
    return 2 * d * h * dh + 2 * d * kv * dh + 3 * d * ff


def _ctx(n: int, window) -> int:
    return n if window is None else min(n, window)


def token_flops(s: dict, pos: int, head: bool) -> float:
    """Operations for the token at position ``pos`` (context ``pos + 1``)."""
    first, last = s["mid"]
    n_mid = last - first
    n_outer = s["layers"] - n_mid
    st = s["stride"]
    attn = 4 * s["heads"] * s["dh"]
    w = (n_outer * _layer_weights(s) + 2 * s["d"] * s["d"]
         + (n_mid * _layer_weights(s) + st * s["d"] * s["d"]) / st)
    f = 2.0 * w + n_outer * attn * _ctx(pos + 1, s["window"])
    f += n_mid * attn * _ctx(pos // st + 1, s["window"]) / st
    if head:
        f += 2.0 * s["d"] * s["vocab"]
    return f


def prompt_flops(s: dict, start: int, end: int) -> float:
    """Prompt positions ``[start, end)`` computed by prefill (those before
    ``start`` came from the prefix cache); the last one needs a logit."""
    return sum(token_flops(s, p, head=(p == end - 1))
               for p in range(start, end))


def decode_flops(s: dict, positions) -> float:
    return sum(token_flops(s, p, head=True) for p in positions)
