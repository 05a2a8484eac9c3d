"""Operations the SOI model's schedule requires, from the configuration's
shapes, for the ``step_mfu`` metrics.

Only what the schedule requires counts, not what the program happens to
execute (a batch-wide middle that fires for one slot, a per-step cast of
the weights, padded chunk rows). The family module's ``token_flops(s, pos,
head)`` counts one token (:mod:`soibench.families`): a token computed by
the model costs 2 x the weights it passes through (every outer layer, the
compress conv and the middle's layers once per ``stride`` tokens, the
fuse, and the LM head where a logit is needed: each decode token and the
last prompt token), plus its attention over the context.
"""

from __future__ import annotations


def prompt_flops(fam, s: dict, start: int, end: int) -> float:
    """Prompt positions ``[start, end)`` computed by prefill (those before
    ``start`` came from the prefix cache); the last one needs a logit."""
    return sum(fam.token_flops(s, p, head=(p == end - 1))
               for p in range(start, end))


def decode_flops(fam, s: dict, positions) -> float:
    return sum(fam.token_flops(s, p, head=True) for p in positions)
