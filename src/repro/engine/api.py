"""Engine protocol: the accelerator functions an outer serving loop calls.

The shape follows JetStream's ``engine_api`` (prefill / insert / generate
with slot-based continuous batching), trimmed to this repo's needs: plain
dataclasses instead of flax structs, greedy sampling, and ``ResultTokens``
packing [token, valid, length] per slot into one (B, 3) array so a single
device->host copy drains a step's results.
"""

from __future__ import annotations

import abc
import dataclasses
from typing import Any, Optional, Tuple

import jax

from repro.engine.contracts import host_get
from repro.obs.spans import span

Params = Any
DecodeState = Any


@dataclasses.dataclass(frozen=True)
class SlotData:
    """One slot's share of a generate step's output."""
    tokens: Any           # (n_tokens,) int32
    valid: Any            # (1,) int32 — 0 for unoccupied slots
    lengths: Any          # (1,) int32 — absolute position after the step
    accepted: Any = None  # (1,) int32 — committed-token count (speculative
    #                       engines; the first ``accepted`` entries of
    #                       ``tokens`` are real). None from per-token engines
    #                       whose single token is always committed.


@dataclasses.dataclass(frozen=True)
class ResultTokens:
    """Tokens emitted by one generate step, one row per slot.

    ``data`` is a single (B, n_cols) int32 array kept as one array so the
    device->host transfer is a single copy; ``logits`` (B, V) rides along
    for sampling/verification harnesses. Per-token engines emit
    [token, valid, length] (the defaults below); speculative engines emit
    up to K tokens per slot — [tok_0..tok_{K-1}, valid, length, accepted] —
    and say so by widening ``tokens_idx`` and setting ``accepted_idx``.

    ``metrics`` (telemetry-enabled engines; else None) is the step's small
    device-side telemetry vector (``repro.engine.step.step_metrics``
    layout): it drains in the SAME batched copy as the tokens, so
    telemetry never adds a device->host transfer to the decode loop.
    """
    data: Any
    logits: Optional[Any] = None
    tokens_idx: tuple = (0, 1)
    valid_idx: tuple = (1, 2)
    length_idx: tuple = (2, 3)
    accepted_idx: Optional[tuple] = None
    metrics: Optional[Any] = None

    def convert_to_numpy(self) -> "ResultTokens":
        """Drain this step's results to host numpy in ONE explicit batched
        transfer (``repro.engine.contracts.host_get``) — the sanctioned
        per-step device->host copy of the serving loop. Call it on the
        *previous* step's results after dispatching the next step, so the
        copy overlaps device compute instead of stalling dispatch. The
        copy is the ``engine.drain`` span (``repro.obs.span``)."""
        with span("engine.drain"):
            data, logits, metrics = host_get((self.data, self.logits,
                                              self.metrics))
        return dataclasses.replace(self, data=data, logits=logits,
                                   metrics=metrics)

    def get_result_at_slot(self, slot: int) -> SlotData:
        return SlotData(
            tokens=self.data[slot, self.tokens_idx[0]:self.tokens_idx[1]],
            valid=self.data[slot, self.valid_idx[0]:self.valid_idx[1]],
            lengths=self.data[slot, self.length_idx[0]:self.length_idx[1]],
            accepted=(None if self.accepted_idx is None else
                      self.data[slot,
                                self.accepted_idx[0]:self.accepted_idx[1]]),
        )


@dataclasses.dataclass(frozen=True)
class Prefix:
    """Result of prefilling one request: batch-1 decode caches positioned at
    ``true_length``, plus the first generated token (greedy over the
    prompt's last real position's logits).

    Bucketed/chunked prefill pads the prompt to a bucket or chunk boundary;
    ``true_length`` is the REAL token count — the decode clock, the paged
    page allocation, and the first-token logits all follow it (pad rows stay
    masked in the caches and never become readable). ``length`` mirrors it
    for unpadded prefills and remains the prompt-length field callers key
    accounting off.

    ``cache_meta`` is prefix-cache bookkeeping attached by engines that
    share prompt-prefix pages across requests (hit boundary, chain keys of
    the prompt's aligned page-block boundaries, SOI carry snapshots): it
    lets ``insert`` map already-resident pages by refcount instead of
    copying, and register the new prefix for future hits. ``None`` from
    engines without a prefix cache.
    """
    state: Any            # batch-1 model decode state (t == true_length)
    first_token: Any      # (1,) int32
    logits: Any           # (1, V) float32 — last real prompt position
    length: int
    true_length: Optional[int] = None
    cache_meta: Optional[dict] = None

    def __post_init__(self):
        if self.true_length is None:
            object.__setattr__(self, "true_length", self.length)


class Engine(abc.ABC):
    """The computational core of the serving loop.

    Implementations must keep ``generate`` a single jitted program per
    config: slot phases / positions are *data* (the per-slot clock vector),
    never trace-time constants.
    """

    @abc.abstractmethod
    def prefill(self, params: Params, tokens: jax.Array) -> Prefix:
        """Compute caches for a prompt; returns a slot-insertable Prefix."""

    @abc.abstractmethod
    def insert(self, prefix: Prefix, decode_state: DecodeState,
               slot: int) -> DecodeState:
        """Write ``prefix`` into batch row ``slot`` of the decode state."""

    @abc.abstractmethod
    def generate(self, params: Params,
                 decode_state: DecodeState) -> Tuple[DecodeState,
                                                     ResultTokens]:
        """Advance every slot by one token (one compiled step)."""

    @abc.abstractmethod
    def init_decode_state(self, params: Params) -> DecodeState:
        """Empty decode state with ``max_concurrent_decodes`` free slots."""

    @abc.abstractmethod
    def free_slot(self, decode_state: DecodeState, slot: int) -> DecodeState:
        """Mark ``slot`` unoccupied (its results become invalid)."""

    @property
    @abc.abstractmethod
    def max_concurrent_decodes(self) -> int:
        """Total slot capacity."""
