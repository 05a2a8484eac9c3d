"""Typed metrics registry + the deferred-drain engine telemetry reader.

Two layers:

* :class:`MetricsRegistry` — plain host-side counters / gauges /
  histograms with stable dotted names (``engine.steps``,
  ``serve.ttft_s``). ``as_dict()`` flattens everything to finite scalars,
  the same shape ``repro.launch.bench`` accepts, so a registry snapshot
  can land in a BENCH file or a metrics JSON unmodified.

* :class:`EngineTelemetry` — the consumer of the engine's *device-side*
  per-step metrics vector. Per-step quantities that live on device (the
  phase-occupancy histogram over ``t % stride``, whether the middle's
  ``lax.cond`` fired, active-slot count, speculative accepted counts)
  are accumulated inside the jitted step (``repro.engine.step
  .step_metrics``), ride back on ``ResultTokens.metrics``, and reach the
  host only through the serving loop's existing ONE deferred drain
  (``ResultTokens.convert_to_numpy`` → ``contracts.host_get``).
  ``observe_result`` therefore REFUSES device arrays: feeding it an
  undrained result would add a blocking device→host copy to the decode
  loop — exactly the host-sync contract ``repro.analysis`` gates.

Telemetry is decode-loop-adjacent, so everything here is numpy/python —
no jax import, nothing that can trace or transfer.
"""

from __future__ import annotations

import numpy as np


def percentile(values, p: float) -> float:
    """p-th percentile as a float; 0.0 on an empty sample (an idle engine
    must report zeros, never NaN)."""
    vals = [float(v) for v in values]
    if not vals:
        return 0.0
    return float(np.percentile(np.asarray(vals, np.float64), p))


class Counter:
    """Monotonically increasing count (events, tokens, cache hits)."""

    kind = "counter"

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def inc(self, n=1):
        if n < 0:
            raise ValueError(f"counter {self.name}: negative increment {n}")
        self.value += n


class Gauge:
    """Last-observed value (pool free pages, compile counts, rates)."""

    kind = "gauge"

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def set(self, v):
        self.value = float(v)


class Histogram:
    """Sample collector summarized as count/mean/p50/p99 (latencies,
    accepted-per-window). Keeps raw samples — serving sessions are short
    enough that bucketing would only lose the tail."""

    kind = "histogram"

    def __init__(self, name: str):
        self.name = name
        self.samples: list = []

    def observe(self, v):
        self.samples.append(float(v))

    def summary(self) -> dict:
        n = len(self.samples)
        return {
            "count": n,
            "mean": float(np.mean(self.samples)) if n else 0.0,
            "p50": percentile(self.samples, 50),
            "p99": percentile(self.samples, 99),
        }


class MetricsRegistry:
    """Get-or-create registry of named metrics.

    Re-requesting a name returns the existing metric; requesting it as a
    different type raises (two call sites silently sharing one name with
    different semantics is how dashboards lie).
    """

    def __init__(self):
        self._metrics: dict = {}

    def _get(self, name: str, cls):
        m = self._metrics.get(name)
        if m is None:
            m = self._metrics[name] = cls(name)
        elif not isinstance(m, cls):
            raise TypeError(f"metric {name!r} already registered as "
                            f"{m.kind}, requested {cls.kind}")
        return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    def as_dict(self) -> dict:
        """Flatten to finite scalars: counters/gauges keep their name,
        histograms expand to ``name.count/.mean/.p50/.p99`` — the flat
        shape ``repro.launch.bench`` validates."""
        out = {}
        for name in sorted(self._metrics):
            m = self._metrics[name]
            if isinstance(m, Histogram):
                for k, v in m.summary().items():
                    out[f"{name}.{k}"] = v
            else:
                v = m.value
                out[name] = int(v) if isinstance(v, (bool, int)) else float(v)
        return out


def _require_numpy(arr, what: str):
    if arr is None:
        return None
    if not isinstance(arr, np.ndarray):
        raise TypeError(
            f"EngineTelemetry needs DRAINED {what} (host numpy), got "
            f"{type(arr).__name__}: call ResultTokens.convert_to_numpy() "
            f"on the *previous* step's result after dispatching the next "
            f"step — reading device values here would add a blocking "
            f"per-step host sync (see docs/OBSERVABILITY.md)")
    return arr


class EngineTelemetry:
    """Accumulates the engine's per-step device metrics vector.

    The vector layout (``repro.engine.step.step_metrics``) for a config
    with SOI stride ``s`` (``s = 1`` for non-SOI configs)::

        [occ_phase_0, ..., occ_phase_{s-1}, mid_fired, n_active]

    where ``occ_phase_p`` counts active slots whose pre-step clock sits at
    ``t % s == p``, ``mid_fired`` is 1 iff the compressed middle's
    ``lax.cond`` executed this step, and ``n_active`` is the live-slot
    count. An *off-phase* step (``mid_fired == 0`` with ``n_active > 0``)
    is the step the paper's schedule saves: the middle's FLOPs were
    skipped for the whole batch. ``off_phase_rate_by_occupancy`` reports
    that skip rate per occupancy level — the scoreboard for phase-aligned
    slot scheduling (ROADMAP: the savings depend on slots clustering by
    ``t % stride``).
    """

    def __init__(self, stride: int, registry: MetricsRegistry | None = None):
        if stride < 1:
            raise ValueError(f"stride must be >= 1, got {stride}")
        self.stride = int(stride)
        self.registry = registry if registry is not None else MetricsRegistry()
        # per-occupancy-level step/off-phase counts: {n_active: [steps, off]}
        self._by_occ: dict = {}
        # phase-coherence accumulators over active steps: how clustered the
        # batch sits on the t % stride circle (modal-bucket slot fraction).
        # The counter handle is resolved once — observe_result runs per
        # decode step and sits inside the serving loop's telemetry budget
        self._coh_steps = 0
        self._coh_full = 0
        self._coh_modal = 0.0
        reg = self.registry
        self._coh_counter = reg.counter("engine.phase_coherent_steps")
        # the rest of the per-step counter handles, resolved once for the
        # same reason (name formatting + dict lookup per decode step was
        # the bulk of observe_result's cost)
        self._c_steps = reg.counter("engine.steps")
        self._c_occ = [reg.counter(f"engine.phase_occupancy.p{p}")
                       for p in range(self.stride)]
        self._c_mid = reg.counter("engine.mid_fired_steps")
        self._c_off = reg.counter("engine.off_phase_steps")

    # -- per-step ----------------------------------------------------------

    def observe_result(self, result) -> None:
        """Fold one DRAINED ``ResultTokens`` into the counters. A result
        without a metrics vector (telemetry-off engine) contributes only
        its speculative accepted counts, if any."""
        reg = self.registry
        met = _require_numpy(getattr(result, "metrics", None), "metrics")
        if met is not None:
            s = self.stride
            if met.shape[-1] != s + 2:
                raise ValueError(
                    f"metrics vector has {met.shape[-1]} entries, expected "
                    f"stride {s} + 2 — telemetry stride mismatch")
            occ = [int(x) for x in met[:s]]
            mid_fired = int(met[s])
            n_active = int(met[s + 1])
            self._c_steps.inc()
            for c, n in zip(self._c_occ, occ):
                c.inc(n)
            if mid_fired:
                self._c_mid.inc()
            elif n_active > 0:
                self._c_off.inc()
            if n_active > 0:
                steps_off = self._by_occ.setdefault(n_active, [0, 0])
                steps_off[0] += 1
                steps_off[1] += 0 if mid_fired else 1
                # coherence: every active slot in ONE t % stride bucket is
                # the state phase-aligned admission maintains — a coherent
                # batch pays the middle once per stride instead of (nearly)
                # every step
                self._coh_steps += 1
                modal = max(occ)
                self._coh_modal += modal / n_active
                if modal == n_active:
                    self._coh_full += 1
                    self._coh_counter.inc()
        if result.accepted_idx is not None:
            data = _require_numpy(result.data, "result data")
            lo, hi = result.accepted_idx
            vlo, vhi = result.valid_idx
            acc = data[:, lo:hi][data[:, vlo:vhi] > 0]
            for a in acc:
                reg.histogram("engine.spec_accepted_per_window").observe(
                    int(a))

    def off_phase_rate_by_occupancy(self) -> dict:
        """{n_active: fraction of that occupancy level's steps whose
        middle was skipped}. Empty until the first active step."""
        return {occ: (off / steps if steps else 0.0)
                for occ, (steps, off) in sorted(self._by_occ.items())}

    def phase_coherence(self) -> dict:
        """How clustered the batch sat on the ``t % stride`` circle, over
        active steps: ``coherent_step_rate`` is the fraction of steps with
        EVERY active slot in one phase bucket (those steps skip the middle
        stride-1 times out of stride); ``modal_fraction_mean`` the mean
        share of active slots in the step's most-populated bucket (1.0 =
        perfectly aligned, ~1/stride = phases uniformly scattered). Zeros
        before the first active step."""
        if not self._coh_steps:
            return {"coherent_step_rate": 0.0, "modal_fraction_mean": 0.0}
        return {"coherent_step_rate": self._coh_full / self._coh_steps,
                "modal_fraction_mean": self._coh_modal / self._coh_steps}

    # -- between steps (host-side state, no device access) -----------------

    def snapshot_engine(self, engine) -> None:
        """Re-register the engine's scattered host-side stats as gauges:
        the traces of each jitted program (``engine.compiles``), the
        weight trees cast to the compute dtype (``engine.param_casts``),
        prefix-cache counters, speculative accept stats, page-pool
        residency, and the sanctioned-drain call count. Reads only host
        ints the engine already tracks — safe at any point of the serving
        loop."""
        reg = self.registry
        for program, n in getattr(engine, "compiles", {}).items():
            reg.gauge(f"engine.compiles.{program}").set(n)
        if hasattr(engine, "param_casts"):
            reg.gauge("engine.param_casts").set(engine.param_casts)
        pc = getattr(engine, "prefix_cache_stats", None)
        if isinstance(pc, dict):
            for k, v in pc.items():
                reg.gauge(f"engine.prefix_cache.{k}").set(v)
        spec_fn = getattr(engine, "spec_accept_stats", None)
        if callable(spec_fn):
            sp = spec_fn()
            if sp.get("speculate") is not None:
                for k in ("windows", "committed", "accept_rate",
                          "tokens_per_window", "draft_candidates",
                          "draft_accepted"):
                    reg.gauge(f"engine.spec.{k}").set(sp[k])
        pools_fn = getattr(engine, "pool_stats", None)
        if callable(pools_fn):
            for group, st in pools_fn().items():
                for k, v in st.items():
                    reg.gauge(f"engine.pages.{group}.{k}").set(v)
        from repro.engine import contracts
        reg.gauge("engine.sanctioned_drains").set(contracts.drain_count())
