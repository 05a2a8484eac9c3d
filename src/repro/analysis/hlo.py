"""Trip-count-aware HLO cost analysis — the parser behind the ``cost`` pass.

XLA's ``compiled.cost_analysis()`` visits a ``while`` body ONCE, so any
scan-over-layers model under-reports FLOPs by ~the layer count (verified in
EXPERIMENTS.md §Roofline). This module parses the optimized HLO text and
computes, per executable:

  * flops            — dot/conv FLOPs, while-bodies multiplied by their trip
                       count (XLA's ``known_trip_count`` annotation when
                       present, otherwise extracted from the loop condition —
                       including bounds carried in the loop tuple, which is
                       where nested scans land after loop-invariant code
                       motion).
  * bytes            — HBM-traffic proxy: sum of operand+result bytes of every
                       scheduled top-level op (fusion internals excluded:
                       they live in registers/VMEM).
  * collective bytes — per collective kind; plus ring-model *wire* bytes
                       (all-reduce 2(n-1)/n, all-gather/reduce-scatter
                       (n-1)/n, all-to-all (n-1)/n, permute 1x) using the
                       replica-group size.

``conditional`` ops are charged for ONE branch, selected by ``cond=``:
``"max"`` (default — the most expensive branch, e.g. a SOI phase-0 step
where the compressed middle runs) or ``"min"`` (the cheapest branch — the
off-phase step where the middle is skipped). Running both modes over the
same program is how ``repro.analysis.cost`` certifies the off-phase FLOP
skip without phase-specialized lowerings.

This is the promoted home of ``benchmarks/hlo_analysis.py`` (which keeps a
thin re-import): the parser itself is pure text processing with no jax
imports, so it also serves stored dry-run artifacts; ``flops_of`` imports
jax lazily.
"""

from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict

# Pure-python registry (no jax import — this module must keep serving
# stored HLO artifacts): closed-form costs for the repo's Pallas kernels.
from repro.kernels import costs as kernel_costs

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->\s*.*{\s*$")
_TRIP_RE = re.compile(r'"known_trip_count":\s*\{"n":"(\d+)"\}')
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_DOT_DIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_WINDOW_RE = re.compile(r"window=\{size=([\dx]+)")
_GTE_INDEX_RE = re.compile(r"index=(\d+)")
_DIRECTION_RE = re.compile(r"direction=(\w+)")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# A Pallas/Mosaic kernel lowers to ONE opaque custom-call: XLA sees no dots
# inside it, so without pricing, a kernel cell would silently drop its
# FLOPs/bytes from the cost certification. Custom-calls with these targets
# MUST resolve to a registered closed-form cost (repro.kernels.costs);
# anything else (Sharding, threefry, ...) is outside the kernel contract
# and stays uncharged, as before.
_KERNEL_CC_TARGETS = ("tpu_custom_call", "mosaic", "triton")
_CC_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_CC_NAME_RE = re.compile(r"name=([\w\-]+)")


def kernel_name_in(text: str) -> str | None:
    """The registered kernel name a custom-call's text carries (its op
    metadata), or None. The longest match wins: the text of a
    ``paged_decode_attention`` call also contains ``decode_attention``."""
    return max((n for n in kernel_costs.KERNEL_COSTS if n in text),
               key=len, default=None)


def _price_custom_call(ins, shapes):
    """(flops/bytes dict | None, unpriced-name | None) for a custom-call.

    (None, None): not a kernel custom-call — ignore. The kernel name is the
    ``pallas_call(name=...)`` string, carried in the op metadata; when
    metadata is stripped, any registered name appearing verbatim in the
    instruction text still matches."""
    mt = _CC_TARGET_RE.search(ins.rest)
    target = mt.group(1) if mt else ""
    if not any(t in target for t in _KERNEL_CC_TARGETS):
        return None, None
    names = _CC_NAME_RE.findall(ins.rest)
    name = next((n for n in names if n in kernel_costs.KERNEL_COSTS), None)
    if name is None:
        name = kernel_name_in(ins.rest)
    if name is None:
        return None, names[0] if names else target

    def _shape(type_str):
        dtype, dims = shape_dims(type_str)
        return kernel_costs.Shape(dtype or "f32", dims,
                                  shape_bytes(type_str))

    ops = [_shape(shapes[o]) for o in ins.operands if o in shapes]
    try:
        return kernel_costs.price(name, _shape(ins.type_str), ops), None
    except (IndexError, ValueError, ZeroDivisionError):
        # operand list didn't match the kernel contract (e.g. a rewrite
        # reordered inputs): surface as unpriced rather than mischarging
        return None, name


def shape_bytes(type_str: str) -> int:
    """Bytes of a (possibly tuple) HLO type string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def shape_dims(type_str: str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None, ()
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    rest: str            # operands + attrs raw text
    operands: tuple


_OPCODE_RE = re.compile(r"([\w\-]+)\((.*)$", re.S)


def _parse_instr(line: str):
    """Manual parse: tuple types contain spaces and '=' (inside /*index=N*/
    comments), so a single regex cannot split type/opcode reliably."""
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    if not s.startswith("%"):
        return None
    eq = s.find(" = ")
    if eq < 0:
        return None
    name = s[1:eq]
    rest = s[eq + 3:]
    if rest.startswith("("):           # tuple type: balanced-paren scan
        depth = 0
        end = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        type_str = rest[:end + 1]
        tail = rest[end + 1:].lstrip()
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        type_str = rest[:sp]
        tail = rest[sp + 1:]
    m = _OPCODE_RE.match(tail)
    if not m:
        return None
    opcode, args = m.groups()
    # operand names = %refs before the closing paren of the operand list
    depth, i = 1, 0
    while i < len(args) and depth > 0:
        if args[i] == "(":
            depth += 1
        elif args[i] == ")":
            depth -= 1
        i += 1
    ops = tuple(_OPERAND_RE.findall(args[:i]))
    return Instr(name, type_str, opcode, args, ops)


def parse_module(text: str) -> dict:
    """name -> list[Instr] for every computation in the module; '__entry__'
    holds the entry computation's name."""
    comps: dict = {}
    current = None
    entry = None
    for line in text.splitlines():
        if not line:
            continue
        if line.rstrip().endswith("{") and "->" in line and "= " not in line[:8]:
            mc = _COMP_RE.match(line)
            if mc:
                current = mc.group(2)
                comps[current] = []
                if mc.group(1):
                    entry = current
                continue
        if line.startswith("}"):
            current = None
            continue
        if current is None:
            continue
        ins = _parse_instr(line)
        if ins is not None:
            comps[current].append(ins)
    comps["__entry__"] = entry
    return comps


def _const_int(ins):
    if ins is None or ins.opcode != "constant":
        return None
    m = re.match(r"(\d+)\)", ins.rest.strip())
    return int(m.group(1)) if m else None


def _resolve_scalar(name, cond_map, while_ins, parent_map, depth=0):
    """Resolve a scalar used by a while CONDITION to a compile-time int.

    Handles the three places a loop bound lives after XLA optimization:
    a literal ``constant`` in the condition computation, behind a chain of
    ``copy``/``convert``s, or — the nested-scan case — CARRIED in the loop
    tuple (loop-invariant code motion hoists the inner scan's bound out of
    its condition, leaving only a ``get-tuple-element``): follow the
    element index back to the while's init tuple in the parent computation
    and read the constant there. Returns None when the value is genuinely
    runtime-dependent."""
    if depth > 8:
        return None
    ins = cond_map.get(name)
    if ins is None:
        return None
    if ins.opcode == "constant":
        return _const_int(ins)
    if ins.opcode in ("copy", "convert", "bitcast") and ins.operands:
        return _resolve_scalar(ins.operands[0], cond_map, while_ins,
                               parent_map, depth + 1)
    if ins.opcode == "get-tuple-element":
        m = _GTE_INDEX_RE.search(ins.rest)
        if not (m and while_ins is not None and parent_map
                and while_ins.operands):
            return None
        idx = int(m.group(1))
        init = parent_map.get(while_ins.operands[0])
        if init is None or init.opcode != "tuple" \
                or idx >= len(init.operands):
            return None
        elem = parent_map.get(init.operands[idx])
        hops = 0
        while (elem is not None and elem.operands and hops < 8
               and elem.opcode in ("copy", "convert", "bitcast")):
            elem = parent_map.get(elem.operands[0])
            hops += 1
        return _const_int(elem)
    return None


def _trip_count(comps, cond_name: str, while_ins=None,
                parent_instrs=None) -> int:
    """Loop trip count from the condition computation's compare.

    jax scans lower to ``i = start; while cmp(i, bound)`` loops. Both sides
    of the compare are resolved through :func:`_resolve_scalar`, so bounds
    carried in the loop tuple (nested scans after hoisting — the
    draft-scan-inside-verify-scan of the speculative window) resolve
    through the init tuple instead of silently collapsing to trip 1. Falls
    back to the legacy max-int-constant heuristic, then 1."""
    instrs = comps.get(cond_name, ())
    cond_map = {i.name: i for i in instrs}
    parent_map = ({i.name: i for i in parent_instrs}
                  if parent_instrs else {})
    compares = [i for i in instrs if i.opcode == "compare"]
    if compares:
        cmp_ins = compares[-1]
        md = _DIRECTION_RE.search(cmp_ins.rest)
        direction = md.group(1) if md else "LT"
        inclusive = 1 if direction in ("LE", "GE") else 0
        vals = [_resolve_scalar(op, cond_map, while_ins, parent_map)
                for op in cmp_ins.operands[:2]]
        resolved = [v for v in vals if v is not None]
        if len(resolved) == 2:
            trip = max(resolved) - min(resolved) + inclusive
            if trip >= 1:
                return trip
        elif len(resolved) == 1 and resolved[0] >= 1:
            # bound resolved, induction start unreachable: jax counts from 0
            return resolved[0] + inclusive
    best = None
    for ins in instrs:
        v = _const_int(ins)
        if v is not None:
            best = v if best is None else max(best, v)
    return best if best else 1


def _group_size(rest: str, num_partitions: int) -> int:
    m = _GROUPS_RE.search(rest)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(rest)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return num_partitions


def _dot_flops(ins: Instr, shapes: dict) -> float:
    lhs = ins.operands[0] if ins.operands else None
    _, rdims = shape_dims(ins.type_str)
    out_elems = math.prod(rdims) if rdims else 1
    m = _DOT_DIMS_RE.search(ins.rest)
    contracted = 1
    if m and lhs in shapes:
        _, ldims = shape_dims(shapes[lhs])
        for idx in m.group(1).split(","):
            if idx:
                contracted *= ldims[int(idx)]
    return 2.0 * out_elems * contracted


def _conv_flops(ins: Instr, shapes: dict) -> float:
    _, rdims = shape_dims(ins.type_str)
    out_elems = math.prod(rdims) if rdims else 1
    kernel = 1
    m = _WINDOW_RE.search(ins.rest)
    if m:
        for s in m.group(1).split("x"):
            kernel *= int(s)
    cin = 1
    if len(ins.operands) >= 2 and ins.operands[1] in shapes:
        _, kd = shape_dims(shapes[ins.operands[1]])
        if kd:
            cin = math.prod(kd) // max(kd[-1], 1) // max(kernel, 1) or 1
    return 2.0 * out_elems * kernel * cin


_SKIP_BYTES = {"parameter", "constant", "get-tuple-element", "tuple",
               "bitcast", "after-all", "partition-id", "replica-id"}

# HBM-traffic ops: on TPU, elementwise chains (convert/broadcast/select/...)
# fuse into producers/consumers, so counting every standalone CPU-backend op
# wildly overstates traffic (and double-counts the CPU's bf16->f32 widening
# round-trips). We count ops that genuinely touch HBM on the TPU plan:
# matmuls/convs, data movement, fusion boundaries, reductions, collectives.
_TRAFFIC_OPS = {"dot", "convolution", "fusion", "copy", "dynamic-slice",
                "dynamic-update-slice", "gather", "scatter", "sort",
                "reduce", "concatenate", "pad", "slice", "iota", "rng",
                "reduce-window", "select-and-scatter", "transpose"}


def analyze(text: str, *, num_partitions: int | None = None,
            cond: str = "max") -> dict:
    """Aggregate costs for the entry computation (per-device numbers, since
    post-SPMD HLO shapes are per-device). ``cond`` selects which branch a
    ``conditional`` is charged for: ``"max"`` (most FLOPs — e.g. the SOI
    phase-0 step) or ``"min"`` (fewest — the off-phase skip)."""
    if cond not in ("max", "min"):
        raise ValueError(f"cond must be 'max' or 'min', got {cond!r}")
    if num_partitions is None:
        m = re.search(r"num_partitions=(\d+)", text)
        num_partitions = int(m.group(1)) if m else 1
    pick = max if cond == "max" else min
    comps = parse_module(text)
    entry = comps.pop("__entry__")
    memo: dict = {}

    def comp_cost(name: str) -> dict:
        if name in memo:
            return memo[name]
        memo[name] = zero = {"flops": 0.0, "bytes": 0.0,
                             "coll_bytes": defaultdict(float),
                             "wire_bytes": 0.0, "unpriced": set()}
        agg = {"flops": 0.0, "bytes": 0.0, "coll_bytes": defaultdict(float),
               "wire_bytes": 0.0, "unpriced": set()}
        instrs = comps.get(name, ())
        shapes = {i.name: i.type_str for i in instrs}

        def add(sub, mult=1.0):
            agg["flops"] += sub["flops"] * mult
            agg["bytes"] += sub["bytes"] * mult
            agg["wire_bytes"] += sub["wire_bytes"] * mult
            agg["unpriced"] |= sub["unpriced"]
            for k, v in sub["coll_bytes"].items():
                agg["coll_bytes"][k] += v * mult

        for ins in instrs:
            op = ins.opcode
            if op == "while":
                body = _BODY_RE.search(ins.rest)
                cnd = _COND_RE.search(ins.rest)
                mt = _TRIP_RE.search(ins.rest)   # XLA's own annotation first
                if mt:
                    trip = int(mt.group(1))
                elif cnd:
                    trip = _trip_count(comps, cnd.group(1), ins, instrs)
                else:
                    trip = 1
                if body:
                    add(comp_cost(body.group(1)), trip)
                if cnd:
                    add(comp_cost(cnd.group(1)), trip)
                continue
            if op in ("call", "async-start"):
                m = re.search(r"to_apply=%?([\w.\-]+)", ins.rest)
                if m:
                    add(comp_cost(m.group(1)))
            if op == "conditional":
                branches = re.findall(r"branch_computations=\{([^}]*)\}",
                                      ins.rest)
                if branches:
                    names = _OPERAND_RE.findall(branches[0])
                    if names:
                        costs = [comp_cost(n) for n in names]
                        add(pick(costs, key=lambda c: c["flops"]))
            if op == "fusion":
                m = _CALLS_RE.search(ins.rest)
                if m:
                    sub = comp_cost(m.group(1))
                    agg["flops"] += sub["flops"]   # dots inside fusions
                    # fusion bytes counted at the fusion boundary below
            if op == "custom-call":
                priced, missing = _price_custom_call(ins, shapes)
                if priced is not None:
                    agg["flops"] += priced["flops"]
                    agg["bytes"] += priced["bytes"]
                elif missing is not None:
                    agg["unpriced"].add(missing)
            if op == "dot":
                agg["flops"] += _dot_flops(ins, shapes)
            elif op == "convolution":
                agg["flops"] += _conv_flops(ins, shapes)
            elif op in ("sort",):
                _, rd = shape_dims(ins.type_str)
                n = math.prod(rd) if rd else 1
                agg["flops"] += n * max(math.log2(max(n, 2)), 1.0)
            if op in COLLECTIVES or any(op.startswith(c + "-start")
                                        for c in COLLECTIVES):
                base = op.replace("-start", "")
                nbytes = shape_bytes(ins.type_str)
                g = _group_size(ins.rest, num_partitions)
                agg["coll_bytes"][base] += nbytes
                if base == "all-reduce":
                    wire = 2.0 * nbytes * (g - 1) / max(g, 1)
                elif base in ("all-gather", "reduce-scatter", "all-to-all"):
                    wire = nbytes * (g - 1) / max(g, 1)
                else:
                    wire = nbytes
                agg["wire_bytes"] += wire
            # HBM byte proxy (fusion-aware, see _TRAFFIC_OPS). Slicing ops
            # move only the slice (XLA aliases the big buffer in place), so
            # charging their full operands would bill every scan iteration
            # for the whole stacked-layers tensor.
            if op in ("dynamic-slice", "gather", "slice"):
                agg["bytes"] += 2.0 * shape_bytes(ins.type_str)
            elif op == "dynamic-update-slice":
                upd = (shapes.get(ins.operands[1])
                       if len(ins.operands) > 1 else None)
                agg["bytes"] += 2.0 * shape_bytes(upd or "f32[]")
            elif op == "scatter":
                upd = (shapes.get(ins.operands[2])
                       if len(ins.operands) > 2 else None)
                agg["bytes"] += 2.0 * shape_bytes(upd or ins.type_str)
            elif op == "fusion":
                # CPU splits elementwise chains into many tiny kLoop fusions;
                # on TPU the chain fuses into one pass whose inputs mostly
                # come from registers/VMEM. Count the write side only — the
                # read side of long-lived buffers is billed at their
                # producing dot/slice/collective.
                agg["bytes"] += shape_bytes(ins.type_str)
            elif op in _TRAFFIC_OPS or op in COLLECTIVES:
                b = shape_bytes(ins.type_str)
                for o in ins.operands:
                    if o in shapes:
                        b += shape_bytes(shapes[o])
                agg["bytes"] += b

        memo[name] = agg
        return agg

    out = comp_cost(entry) if entry else {"flops": 0, "bytes": 0,
                                          "coll_bytes": {}, "wire_bytes": 0,
                                          "unpriced": set()}
    out = dict(out)
    out["coll_bytes"] = dict(out["coll_bytes"])
    # kernel custom-calls (Pallas/Mosaic targets) with no registered cost:
    # consumers (repro.analysis.cost) fail loudly on a non-empty list — an
    # unpriced kernel would silently vanish from the certification
    out["unpriced_custom_calls"] = sorted(out.pop("unpriced"))
    out["num_partitions"] = num_partitions
    return out


def flops_of(fn, *args):
    """Trip-count-aware FLOPs of ``jit(fn)`` lowered on ``args`` (XLA's own
    cost_analysis visits scan bodies once, under-reporting layer-scanned
    models — see module docstring). jax imported lazily: the rest of this
    module stays usable as a pure-text parser for stored dry-run artifacts."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    return analyze(compiled.as_text())["flops"]
