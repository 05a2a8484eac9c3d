"""The admission loop, on the wall clock.

It drives ``SOIEngine``'s serving surface (``can_insert`` / ``prefill`` /
``insert`` / ``generate`` / ``free_slot``) the way ``repro.obs.loadgen.
run_load`` does: admission through ``can_insert`` (pool pressure defers,
phase-aligned admission waits at most ``2 * stride`` steps), one generate
step per iteration, and the previous step's tokens drained while the next
one runs. Unlike ``run_load`` it never skips time: an open loop's request
is timed from when it was due, and the loop sleeps through idle gaps.

Each call into the engine is a host span: kept in memory (name, start,
end) and written into the profiler's trace as a ``TraceAnnotation`` named
``bench.<call>``, so a traced run can attribute device gaps to them.
"""

from __future__ import annotations

import collections
import contextlib
import time

import numpy as np

from repro.engine import SOIEngine

clock = time.perf_counter


def make_engine(cfg, mix: dict) -> SOIEngine:
    e = mix["engine"]
    return SOIEngine(cfg, max_concurrent_decodes=e["slots"],
                     max_len=e["max_len"], paged=True,
                     page_size=e["page_size"], n_pages=e.get("n_pages"),
                     n_pages_mid=e.get("n_pages_mid"),
                     prefill_chunk=e["chunk"],
                     prefix_cache=e["prefix_cache"], telemetry=True)


class Spans:
    """Host spans of the benchmark's calls into the engine."""

    def __init__(self):
        self.spans = []                 # (name, start, end), clock seconds
        import jax.profiler
        self._annotation = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = clock()
        with self._annotation(f"bench.{name}"):
            yield
        self.spans.append((name, t0, clock()))


class Loop:
    """One engine, one live decode state, one serving session."""

    def __init__(self, engine: SOIEngine, params, mix: dict):
        self.engine = engine
        self.params = params
        self.mix = mix
        self.chunk = mix["engine"]["chunk"]
        self.phase_align = bool(mix["engine"]["phase_align"])
        self.stride = engine.cfg.soi.stride
        self.state = engine.init_decode_state(params)
        self.free = collections.deque(range(engine.max_concurrent_decodes))
        self.active: dict = {}          # slot -> Request
        self.waiting: collections.deque = collections.deque()
        self.pending = None             # (ResultTokens, {slot: rid})
        self.spans = Spans()
        self.steps: list = []           # (drain time, telemetry vector)
        self.counts = collections.Counter()
        self.finished: list = []
        self._streak = 0

    # -- engine calls -----------------------------------------------------

    def padded(self, tokens: np.ndarray) -> np.ndarray:
        """The prompt right-padded to a whole number of chunks (prefill
        takes ``true_length``), so no pad amount compiles a program."""
        n = -(-len(tokens) // self.chunk) * self.chunk
        out = np.zeros((1, n), np.int32)
        out[0, :len(tokens)] = tokens
        return out

    def prefill(self, tokens: np.ndarray):
        with self.spans("prefill"):
            return self.engine.prefill(self.params, self.padded(tokens),
                                       true_length=len(tokens))

    def _admit_one(self, req, slot: int):
        req.t_prefill = clock()
        try:
            prefix = self.prefill(req.tokens)
            req.cached = (prefix.cache_meta or {}).get("hit", 0)
            with self.spans("insert"):
                self.state = self.engine.insert(prefix, self.state, slot)
            with self.spans("first_token"):
                first = int(prefix.first_token[0])
        except (RuntimeError, ValueError) as e:
            req.error = f"{type(e).__name__}: {e}"
            self.state = self.engine.live_decode_state
            self.free.append(slot)
            self.counts["failed"] += 1
            self.finished.append(req)
            return
        req.out.append(first)
        req.times.append(clock())
        self.active[slot] = req
        if req.done:
            self._finish(slot)

    def _finish(self, slot: int):
        req = self.active.pop(slot)
        with self.spans("free_slot"):
            self.state = self.engine.free_slot(self.state, slot)
        self.free.append(slot)
        self.finished.append(req)
        self.on_done(req)

    def on_done(self, req):
        """Called when a request has all its tokens (closed loops issue
        the client's next request here)."""

    def admit(self):
        eng = self.engine
        while self.waiting and self.free:
            req, slot = self.waiting[0], self.free[0]
            tl = len(req.tokens)
            if not eng.can_insert(tl, slot):
                self.counts["pool_deferred"] += 1
                return
            if (self.phase_align and self._streak < 2 * self.stride
                    and not eng.can_insert(tl, slot, phase_align=True)):
                # the batch phase comes round within stride - 1 steps;
                # the streak cap admits misaligned rather than starve
                self.counts["phase_deferred"] += 1
                self._streak += 1
                return
            self._streak = 0
            self.waiting.popleft()
            self.free.popleft()
            self._admit_one(req, slot)

    def step(self):
        """Dispatch one generate step, then drain the previous one."""
        snapshot = {slot: req.rid for slot, req in self.active.items()}
        with self.spans("generate"):
            self.state, result = self.engine.generate(self.params,
                                                      self.state)
        if self.pending is not None:
            self.drain()
        self.pending = (result, snapshot)

    def drain(self):
        result, snapshot = self.pending
        self.pending = None
        with self.spans("drain"):
            res = result.convert_to_numpy()
        t = clock()
        self.steps.append((t, np.asarray(res.metrics)))
        for slot, rid in snapshot.items():
            req = self.active.get(slot)
            if req is None or req.rid != rid or req.done:
                continue
            req.out.append(int(res.data[slot, 0]))
            req.times.append(t)
            if req.done:
                self._finish(slot)

    def flush(self):
        if self.pending is not None:
            self.drain()


class ClosedLoop(Loop):
    """``clients`` clients, each sending its next request the moment the
    last one completes (no think time). A request is due when sent."""

    def __init__(self, engine, params, mix, traffic, t_zero: float):
        super().__init__(engine, params, mix)
        self.traffic = traffic
        self.t_zero = t_zero
        self.issuing = False

    def send(self, client: int, t: float):
        req = self.traffic.take(client)
        req.due = t - self.t_zero
        self.waiting.append(req)

    def on_done(self, req):
        if self.issuing:
            self.send(req.client, clock())

    def preroll(self, slots: int, clients: int):
        """Fill every slot, with the first requests' outputs cut to
        staggered lengths so the slots free at spread times, as they do
        in a loop that has run for a while."""
        t = clock()
        self.issuing = True
        for c in range(clients):
            self.send(c, t)
        for i, req in enumerate(list(self.waiting)[:slots]):
            req.gen_len = max(2, int(round(req.gen_len * (i + 0.5) / slots)))
        while len(self.active) < min(slots, clients) and self.waiting:
            self.admit()
            if self.active:
                self.step()

    def run(self, t_open: float, seconds: float):
        # from here on a request is due at seconds after the window opened
        shift = self.t_zero - t_open
        for r in list(self.waiting) + list(self.active.values()):
            r.due += shift
        self.t_zero = t_open
        close = t_open + seconds
        while clock() < close:
            self.admit()
            if self.active:
                self.step()
        self.issuing = False
        self.flush()


class OpenLoop(Loop):
    """Requests sent on a schedule, whatever the server does; each is
    timed from when it was due. ``lateness`` is how long after its due
    time the loop noticed each request."""

    def __init__(self, engine, params, mix, schedule: list):
        super().__init__(engine, params, mix)
        self.schedule = collections.deque(schedule)
        self.lateness: list = []
        self.backlog_at_close = None    # requests due but not admitted

    def release(self, t_open: float):
        now = clock() - t_open
        while self.schedule and self.schedule[0].due <= now:
            req = self.schedule.popleft()
            self.lateness.append(now - req.due)
            self.waiting.append(req)

    def run(self, t_open: float, seconds: float, grace: float):
        """Serve until every request due in the window has all its
        tokens, or ``grace`` seconds past the close."""
        close, deadline = t_open + seconds, t_open + seconds + grace
        while clock() < deadline:
            if self.backlog_at_close is None and clock() >= close:
                self.backlog_at_close = len(self.waiting)
            self.release(t_open)
            self.admit()
            if self.active:
                self.step()
                continue
            self.flush()
            if not self.schedule and not self.waiting and not self.active:
                break
            if self.schedule and not self.waiting:
                wait = t_open + self.schedule[0].due - clock()
                if wait > 0:
                    with self.spans("idle"):
                        time.sleep(min(wait, 0.001))
        if self.backlog_at_close is None:       # all served before the close
            self.backlog_at_close = 0
        self.flush()
