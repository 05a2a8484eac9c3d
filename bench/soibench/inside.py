"""The program's own spans and scopes in the traced window.

The engine marks its host work with ``repro.obs.span`` (``engine.generate``
with its args ``step``, ``mid``, ``active``, ...; ``engine.dispatch`` around
each jitted call), and its compiled programs name their parts with
``jax.named_scope`` (``cast_params``, ``soi_pre``, ``soi_middle``,
``soi_post``, ``lm_head``). :func:`load` reads both from the window's
``.xplane.pb`` into a plain record (:class:`Inside`, JSON-able, which the
tests keep as a recorded trace):

* ``spans``: every ``engine.*`` host event, with its args;
* ``bench``: the benchmark's own ``bench.<call>`` spans, which place the
  window on the trace's clock (``profile.clock_offset``);
* ``programs``: every event on the first TPU's "XLA Modules" line;
* ``ops``: every event on its "XLA Ops" line but ``while`` and
  ``conditional`` containers (their own ops are events too), with the
  scope it ran under, read from the ``op_name`` the compiled module's HLO
  (kept in the trace's metadata plane) gives the instruction.

Engine spans and device events share the trace's own clock. A program
without these spans or scopes (one from before they were added) gives an
empty list or a None scope, and the readers return None.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import pathlib

from soibench import profile

SCOPES = ("cast_params", "soi_pre", "soi_middle", "soi_post", "lm_head")
TRACE_DIR = pathlib.Path("bench_trace")     # where profile.Tracer writes
# how far the trace's device clock may run ahead of its host clock (ns); a
# step's execution starts at least a drain (several ms on TPU v5e) after
# the previous step's dispatch
SKEW_NS = 2e6


@dataclasses.dataclass
class Inside:
    """The parts of a trace these readers use (times in ns)."""
    spans: list         # [name, start, dur, args]    engine.* on the host
    bench: list         # [name, start, dur]          bench.<call> on the host
    programs: list      # [name, start, dur]          "XLA Modules"
    ops: list           # [name, start, dur, scope]   "XLA Ops"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Inside":
        return cls(d["spans"], d["bench"], d["programs"], d["ops"])


# -- protobuf wire format, for the HLO the trace's metadata plane keeps ----

def _fields(buf):
    """(field number, value) of one message (a ``memoryview``): ints for
    varint and fixed fields, views for length-delimited ones (nested
    messages, strings), so nothing is copied."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _first(buf: bytes, num: int, default=b""):
    return next((v for k, v in _fields(buf) if k == num), default)


def hlo_op_names(xspace) -> dict:
    """``{module name: {instruction name: op_name}}`` from the ``Hlo
    Proto`` stats of the ``/host:metadata`` plane of a serialized XSpace
    (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map, value
    = 2), .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5;
    XStat.metadata_id = 1, .bytes_value = 6; HloProto.hlo_module = 1;
    HloModuleProto.computations = 3; HloComputationProto.instructions = 2;
    HloInstructionProto.name = 1, .metadata = 7; OpMetadata.op_name = 2)."""
    out = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1 or _first(plane, 2) != b"/host:metadata":
            continue
        for k, entry in _fields(plane):
            if k != 4:
                continue
            md = _first(entry, 2)
            module = bytes(_first(md, 2)).decode()
            for s, stat in _fields(md):
                proto = _first(stat, 6) if s == 5 else b""
                if proto:
                    out[module] = _module_op_names(_first(proto, 1))
    return out


def _module_op_names(module: bytes) -> dict:
    names = {}
    for k, comp in _fields(module):
        if k != 3:
            continue
        for j, inst in _fields(comp):
            if j == 2:
                op_name = _first(_first(inst, 7), 2)
                names[bytes(_first(inst, 1)).decode()] = \
                    bytes(op_name).decode()
    return names


def scope_of(op_name: str | None) -> str | None:
    """The innermost of :data:`SCOPES` on an ``op_name`` path."""
    if not op_name:
        return None
    return next((p for p in reversed(op_name.split("/")) if p in SCOPES),
                None)


# -- reading the trace --------------------------------------------------------

def load(path: str) -> Inside:
    """Read an ``.xplane.pb`` into :class:`Inside` (first TPU only)."""
    from jax.profiler import ProfileData
    data = pathlib.Path(path).read_bytes()
    names = hlo_op_names(data)
    pd = ProfileData.from_serialized_xspace(data)
    planes = profile._tpu_planes(pd)
    if not planes:
        raise ValueError(f"no TPU plane in {path}")
    programs, ops = [], []
    for line in planes[0].lines:
        if line.name == "XLA Modules":
            for e in line.events:
                programs.append([e.name, e.start_ns, e.duration_ns])
    runs = sorted((p[1], p[1] + p[2], p[0]) for p in programs)
    starts = [r[0] for r in runs]
    tables = {}
    for line in planes[0].lines:
        if line.name != "XLA Ops":
            continue
        for e in line.events:
            name, _, _, container = profile.parse_op(e.name)
            if container:
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            module = runs[i][2] if i >= 0 else ""
            if module not in tables:
                tables[module] = names.get(module) or _by_base(names, module)
            table = tables[module]
            ops.append([name, e.start_ns, e.duration_ns,
                        scope_of(table.get(name))])
    spans, bench = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    spans.append([e.name, e.start_ns, e.duration_ns,
                                  dict(e.stats)])
                elif e.name.startswith("bench."):
                    bench.append([e.name[len("bench."):], e.start_ns,
                                  e.duration_ns])
    spans.sort(key=lambda s: s[1])
    bench.sort(key=lambda s: s[1])
    return Inside(spans, bench, programs, ops)


def _by_base(names: dict, module: str) -> dict:
    """The op-name table of the last module of the same base name
    (``jit__gen``), where the trace keys its HLO by another id."""
    base = module.split("(")[0]
    found = [v for k, v in names.items() if k.split("(")[0] == base]
    return found[-1] if found else {}


@functools.lru_cache(maxsize=1)
def _load_cached(path: str, mtime: float) -> Inside:
    return load(path)


def of(run) -> Inside | None:
    """The :class:`Inside` of a traced run's window, or None when the run
    was not traced."""
    if run.trace is None:
        return None
    paths = sorted(glob.glob(str(TRACE_DIR.resolve() / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return None
    return _load_cached(paths[-1], os.path.getmtime(paths[-1]))


def window(ins: Inside, run) -> tuple:
    """The run's window ``[lo, hi)`` on the trace's clock (ns)."""
    offset = profile.clock_offset(profile.Events([], [], ins.bench),
                                  run.loop.spans.spans, run.t_open)
    return (int((run.t_open + offset) * 1e9),
            int((run.t_close + offset) * 1e9))


# -- reductions -----------------------------------------------------------------

def executions(ins: Inside, program: str, lo: float, hi: float) -> list:
    """(start, end) of every execution of ``program`` (``jit__gen``) begun
    in ``[lo, hi)``."""
    return sorted((s, s + d) for n, s, d in ins.programs
                  if n.split("(")[0] == program and lo <= s < hi)


def steps(ins: Inside, lo: float, hi: float) -> list:
    """(args of the ``engine.generate`` span, (start, end) of its
    ``jit__gen`` execution) of every step begun in ``[lo, hi)``.

    The device runs the steps in the order the host dispatched them, one
    execution each, so the i-th step pairs with the (i + k)-th execution
    of the trace: k executions were dispatched before the trace began.
    The trace's host and device clocks can disagree by a millisecond or
    two, so an execution that starts as soon as it is dispatched can
    appear to start just before its ``engine.dispatch``; k is the offset,
    of 0 to 2, under which most executions start no earlier than
    :data:`SKEW_NS` before their dispatch and before the next step's."""
    gens = [s for s in ins.spans if s[0] == "engine.generate"]
    disp = sorted(s[1] for s in ins.spans if s[0] == "engine.dispatch"
                  and s[3].get("program") == "gen")
    marks = []
    for g in gens:
        i = bisect.bisect_left(disp, g[1])
        if i < len(disp) and disp[i] <= g[1] + g[2]:
            marks.append((disp[i], g))
    runs = executions(ins, "jit__gen", float("-inf"), float("inf"))

    def fits(k):
        return sum(1 for j, (d, _) in enumerate(marks)
                   if j + k < len(runs) and d - SKEW_NS <= runs[j + k][0]
                   and (j + 1 == len(marks)
                        or runs[j + k][0] < marks[j + 1][0]))

    k = max(range(3), key=lambda k: (fits(k), -k))
    return [(g[3], runs[j + k]) for j, (_, g) in enumerate(marks)
            if j + k < len(runs) and lo <= g[1] < hi
            and lo <= runs[j + k][0] < hi]


def scope_ns(ins: Inside, program: str, scope: str | None, lo: float,
             hi: float) -> tuple:
    """(device ns of the ops under ``scope`` inside the executions of
    ``program`` begun in ``[lo, hi)``, those executions' device ns, their
    count). None when no op of the trace carries any scope."""
    if not any(o[3] for o in ins.ops):
        return None
    runs = executions(ins, program, lo, hi)
    starts = [r[0] for r in runs]
    total = 0.0
    for _, s, d, sc in ins.ops:
        if sc != scope:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            total += d
    return total, float(sum(b - a for a, b in runs)), len(runs)


# -- what the readers in bench/metrics read ---------------------------------

def read(run, fn, *args):
    """``fn(ins, lo, hi, *args)`` over the run's window, or None when the
    run was not traced."""
    ins = of(run)
    if ins is None:
        return None
    return fn(ins, *window(ins, run), *args)


def host_ms(ins: Inside, lo: float, hi: float):
    """Mean duration of the ``engine.generate`` spans begun in
    ``[lo, hi)`` (ms), or None."""
    d = [s[2] for s in ins.spans if s[0] == "engine.generate"
         and lo <= s[1] < hi]
    return 1e-6 * sum(d) / len(d) if d else None


def step_ms(ins: Inside, lo: float, hi: float, mid: int):
    """Mean device time of the ``jit__gen`` executions of the steps begun
    in ``[lo, hi)`` whose generate span has ``mid`` = ``mid`` (ms), or
    None."""
    d = [b - a for args, (a, b) in steps(ins, lo, hi)
         if args.get("mid") == mid]
    return 1e-6 * sum(d) / len(d) if d else None


def scope_ms(ins: Inside, lo: float, hi: float, program: str, scope: str):
    """Device time of the ops under ``scope`` per execution of ``program``
    begun in ``[lo, hi)`` (ms), or None."""
    found = scope_ns(ins, program, scope, lo, hi)
    if found is None or not found[2]:
        return None
    return 1e-6 * found[0] / found[2]
