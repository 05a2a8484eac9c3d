"""What one run measured, as its metric readers see it."""

from __future__ import annotations

import dataclasses

import numpy as np

from soibench import flops


def percentile(values, q: float):
    """The ``q``-th percentile (linear between ranks), None when empty."""
    return float(np.percentile(values, q)) if len(values) else None


@dataclasses.dataclass
class Run:
    cell: object                # soibench.spec.Cell
    sizes: dict                 # the family's sizes of the config
    seed: int
    t_open: float               # window, on serve.clock
    t_close: float
    setup_s: float
    requests: list              # every request sent, finished or not
    loop: object                # the soibench.serve loop that served them
    peak: dict                  # the device's row of bench/peaks.json
    memory_peak_bytes: int
    trace: object = None        # soibench.profile.Summary of a traced run

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def inside(self, t: float) -> bool:
        return self.t_open <= t < self.t_close

    def token_gaps(self) -> np.ndarray:
        """Every gap between consecutive output tokens of a request, as the
        client saw them, that ended inside the window (seconds)."""
        out = []
        for r in self.requests:
            t = np.asarray(r.times)
            if len(t) > 1:
                g = np.diff(t)
                out.append(g[(t[1:] >= self.t_open) & (t[1:] < self.t_close)])
        return np.concatenate(out) if out else np.zeros(0)

    def tokens_in_window(self) -> int:
        return sum(1 for r in self.requests for t in r.times
                   if self.inside(t))

    def due_in_window(self) -> list:
        return [r for r in self.requests
                if 0.0 <= r.due < self.seconds]

    def ttfts(self) -> np.ndarray:
        """First token on the host minus the time it was due, over requests
        due in the window that got one (seconds)."""
        return np.array([r.times[0] - (self.t_open + r.due)
                         for r in self.due_in_window() if r.times])

    def steps_in_window(self) -> np.ndarray:
        """Telemetry vectors of the generate steps drained in the window."""
        rows = [m for t, m in self.loop.steps if self.inside(t)]
        return np.stack(rows) if rows else np.zeros((0, 0))

    def spans(self, name: str) -> list:
        return [(a, b) for n, a, b in self.loop.spans.spans
                if n == name and self.inside(a)]

    def program_ms(self, program: str):
        """Device time per execution of ``program`` in the traced window
        (ms), or None."""
        t = self.trace and self.trace.programs.get(program)
        return 1e3 * t[1] / t[0] if t and t[0] else None

    def program_mfu(self, program: str, which: str):
        """Operations the schedule requires for the window's ``which`` work
        over the device time of ``program`` times the peak (%), or None."""
        t = self.trace and self.trace.programs.get(program)
        f = self.model_flops(which)
        if not t or not t[1] or not f:
            return None
        return 100.0 * f / (t[1] * self.peak["bf16_flops_s"])

    def model_flops(self, which: str = "all") -> float:
        """Operations the schedule requires for the work of the window:
        prompts whose prefill began in it (prefix-cache hits excluded) and
        decode tokens delivered in it."""
        fam, s, total = self.cell.family, self.sizes, 0.0
        for r in self.requests:
            if which in ("all", "prompt") and self.inside(r.t_prefill):
                total += flops.prompt_flops(fam, s, r.cached, len(r.tokens))
            if which in ("all", "decode"):
                tl = len(r.tokens)
                pos = [tl + k - 1 for k, t in enumerate(r.times)
                       if k and self.inside(t)]
                total += flops.decode_flops(fam, s, pos)
        return total
