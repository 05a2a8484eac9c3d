"""From a profiler trace of the window to per-layer numbers.

:class:`Tracer` records the window with ``jax.profiler``. :func:`load`
reads the ``.xplane.pb`` it wrote into a small plain record (:class:`Events`,
JSON-able, which the tests keep as a recorded trace):

* device ops: every event on the first TPU's "XLA Ops" line, with the
  compiled program it ran in, its kernel name where it is a registered
  Pallas kernel, and its operand shapes where the trace gives its HLO text;
* programs: every event on that TPU's "XLA Modules" line;
* host spans: the benchmark's own ``bench.<call>`` annotations.

:func:`reduce` turns those into a :class:`Summary`: the device's busy
seconds, time per program execution, time and roofline share per kernel,
idle share against the time a request was in flight, and the ``breakdown``
(the ops that took most device time, and the longest idle gaps named by
the host span they fell in). All times are the trace's own clock.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import math
import re

from soibench import costs

_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|"
                    r"f64|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
             "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
             "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def kernel_of(name: str, table: dict = costs.KERNELS) -> str | None:
    """The kernel of ``table`` (name -> cost formula; the cell's is
    :func:`soibench.families.kernel_costs`) an HLO instruction name
    (``paged_decode_attention.24``) belongs to; the longest match, so
    ``paged_decode_attention`` is not read as a shorter one."""
    return max((k for k in table if name.startswith(k)), key=len,
               default=None)


def hlo_shapes(text: str):
    """(result shape, operand shapes) of one custom-call instruction's HLO
    text, or None. Shapes are read up to ``custom_call_target``, so the
    layout constraints after it do not count."""
    head, sep, rest = text.partition("custom-call(")
    if not sep:
        return None
    rest = rest.split("custom_call_target=", 1)[0]

    def shapes(s):
        return [costs.Shape(tuple(int(x) for x in dims.split(",") if x),
                            _ITEMSIZE[dt]) for dt, dims in _SHAPE.findall(s)]

    out = shapes(head.split("=", 1)[-1])
    ops = shapes(rest)
    return (out[0], ops) if out and ops else None


def _as_lists(out, args) -> list:
    return [[list(out.dims), out.itemsize],
            [[list(a.dims), a.itemsize] for a in args]]


def parse_op(text: str, table: dict = costs.KERNELS) -> list:
    """[name, kernel, shapes, container] of one "XLA Ops" event, whose
    name is the instruction's HLO text: its instruction name, the Pallas
    kernel it calls (if any) with its operand shapes, and whether it is a
    ``while`` or ``conditional`` whose own ops are events of their own."""
    name = text.split(" = ", 1)[0].lstrip("%")
    kernel = kernel_of(name, table) if "custom-call(" in text else None
    shp = hlo_shapes(text) if kernel else None
    container = " while(" in text or " conditional(" in text
    return [name, kernel, _as_lists(*shp) if shp else None, container]


@dataclasses.dataclass
class Events:
    """The parts of a trace the reduction reads (times in ns)."""
    ops: list          # [name, start, dur, kernel, shapes, container]
    programs: list     # [name, start, dur]
    spans: list        # [name, start, dur]   (bench.<call> on the host)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        return cls(d["ops"], d["programs"], d["spans"])


def _tpu_planes(pd):
    return sorted((p for p in pd.planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: p.name)


def load(path: str, table: dict = costs.KERNELS) -> Events:
    """Read an ``.xplane.pb`` into :class:`Events` (first TPU only), with
    the kernels of ``table``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = _tpu_planes(pd)
    if not planes:
        raise ValueError(f"no TPU plane in {path}")
    ops, programs, spans = [], [], []
    for line in planes[0].lines:
        if line.name == "XLA Modules":
            for e in line.events:
                programs.append([e.name, e.start_ns, e.duration_ns])
        elif line.name == "XLA Ops":
            for e in line.events:
                name, kernel, shp, container = parse_op(e.name, table)
                ops.append([name, e.start_ns, e.duration_ns, kernel, shp,
                            container])
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    spans.append([e.name[len("bench."):], e.start_ns,
                                  e.duration_ns])
    spans.sort(key=lambda s: s[1])
    return Events(ops, programs, spans)


def _union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


def _total(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    idle_share: float | None       # against time with a request in flight
    programs: dict                 # name -> (calls, seconds)
    kernels: dict                  # name -> (calls, seconds, least seconds)
    breakdown: dict


def reduce(ev: Events, lo: int, hi: int, in_flight, peak: dict,
           table: dict = costs.KERNELS) -> Summary:
    """Numbers of the traced window ``[lo, hi)`` (trace ns). ``in_flight``
    are the (start, end) ns during which some request was in flight;
    ``table`` gives the cost formula of each kernel in ``ev``."""
    ops = [o for o in ev.ops if o[1] < hi and o[1] + o[2] > lo]
    busy = _clip(_union([o[1], o[1] + o[2]] for o in ops if not o[5]),
                 lo, hi)
    busy_ns = _total(busy)
    flight = _clip(_union([list(x) for x in in_flight]), lo, hi)
    flight_ns = _total(flight)
    busy_in_flight = 0.0
    for a, b in flight:
        busy_in_flight += _total(_clip(busy, a, b))
    idle = None if not flight_ns else 1.0 - busy_in_flight / flight_ns

    programs = collections.defaultdict(lambda: [0, 0.0])
    runs = sorted((start, start + dur, name.split("(")[0])
                  for name, start, dur in ev.programs)
    for start, end, key in runs:
        if lo <= start < hi:                # executions begun in the window
            programs[key][0] += 1
            programs[key][1] += (end - start) / 1e9
    starts = [r[0] for r in runs]

    def program_of(t):
        i = bisect.bisect_right(starts, t) - 1
        return runs[i][2] if i >= 0 and t < runs[i][1] else "no program"

    kernels = collections.defaultdict(lambda: [0, 0.0, 0.0])
    per_op = collections.defaultdict(float)
    for name, start, dur, kernel, shp, container in ops:
        if container:
            continue
        per_op[f"{program_of(start)}:{kernel or name}"] += dur / 1e9
        if kernel is None:
            continue
        k = kernels[kernel]
        k[0] += 1
        k[1] += dur / 1e9
        if shp is not None and k[2] is not None:
            out = costs.Shape(tuple(shp[0][0]), shp[0][1])
            args = [costs.Shape(tuple(d), s) for d, s in shp[1]]
            k[2] += costs.least_seconds(table[kernel](out, args), peak)
        else:
            k[2] = None            # a call without shapes: no roofline

    gaps = []
    for (a0, b0), (a1, b1) in zip(busy, busy[1:]):
        gaps.append((a1 - b0, b0, a1))
    spans = [(n, s, s + d) for n, s, d in ev.spans]
    named = []
    for length, a, b in sorted(gaps, reverse=True)[:10]:
        mid = (a + b) / 2
        host = [n for n, s, e in spans if s <= mid < e]
        named.append([host[-1] if host else "no benchmark span",
                      length / 1e9])
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return Summary(
        window_s=(hi - lo) / 1e9, busy_s=busy_ns / 1e9, idle_share=idle,
        programs={k: tuple(v) for k, v in programs.items()},
        kernels={k: tuple(v) for k, v in kernels.items()},
        breakdown={"device_ops": [[n, s] for n, s in top],
                   "idle_gaps": named})


class Tracer:
    """Profiles the window. The host spans it annotates give the offset
    between the loop's clock and the trace's."""

    def __init__(self, directory, table: dict = costs.KERNELS):
        self.dir = directory
        self.table = table      # the cell's kernels and their costs

    def start(self):
        import jax
        jax.profiler.start_trace(str(self.dir))

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def events(self) -> Events:
        paths = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no trace under {self.dir}")
        return load(sorted(paths)[-1], self.table)

    def summary(self, run) -> Summary:
        """The window of ``run`` (a :class:`soibench.window.Run`)."""
        ev = self.events()
        offset = clock_offset(ev, run.loop.spans.spans, run.t_open)

        def ns(t):
            return int((t + offset) * 1e9)

        lo, hi = ns(run.t_open), ns(run.t_close)
        # a request is in flight from when it was due to its last token
        flight = [(ns(run.t_open + r.due),
                   ns(r.times[-1]) if r.times else hi)
                  for r in run.requests if not math.isnan(r.due)]
        return reduce(ev, lo, hi, flight, run.peak, self.table)


def clock_offset(ev: Events, host_spans: list, t_open: float) -> float:
    """Seconds to add to the loop's clock to get the trace's: the median
    over the window's spans, matched in order, of their start times'
    difference."""
    mine = [(n, a) for n, a, _ in host_spans if a >= t_open]
    diffs = [s / 1e9 - a for (n, a), (m, s, _) in zip(mine, ev.spans)
             if n == m]
    if not diffs:
        raise ValueError("no benchmark span in the trace")
    diffs.sort()
    return diffs[len(diffs) // 2]

