"""Dtype-drift checker (DT0xx).

The decode state is a long-lived carry: a single promotion or narrowing
inside one step compounds across thousands of steps (silent precision loss)
or doubles cache memory (silent f32 upcast of a bf16 ring).  Three checks
per ``JitEntry``:

* **carry stability** (DT001): for entries that thread the decode state
  through (``carry=(in_argnum, out_index)``), ``jax.eval_shape`` compares
  every state leaf's dtype/weak-type on the way in vs the way out — the
  carry must be a fixed point;
* **narrowing** (DT002): the jaxpr is walked (recursing into scan/while/
  cond/pjit sub-jaxprs) for ``convert_element_type`` equations that narrow
  a float below the config's compute dtype — e.g. an accidental f32->bf16
  round-trip inside attention;
* **widening / weak types** (DT003): any float64 value anywhere in the
  program (x64 leaking in doubles memory and is usually a Python-float
  promotion), and any output leaf that became weakly-typed when its input
  was strong (weak types poison downstream cache keys and promotions).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr
from jax.tree_util import keystr, tree_flatten_with_path

from repro.analysis.report import Finding


def _float_itemsize(dtype) -> int:
    dt = np.dtype(dtype)
    # np.dtype.kind is 'V' for ml_dtypes floats (bfloat16, fp8): go
    # through jax's dtype lattice instead of the numpy kind char
    return dt.itemsize if jnp.issubdtype(dt, jnp.floating) else 0


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in _as_jaxprs(param):
                yield from _iter_eqns(sub)


def _as_jaxprs(param):
    if isinstance(param, ClosedJaxpr):
        return [param.jaxpr]
    if isinstance(param, Jaxpr):
        return [param]
    if isinstance(param, (list, tuple)):
        out = []
        for p in param:
            out.extend(_as_jaxprs(p))
        return out
    return []


def _walk_program(target_name, entry, compute_itemsize) -> list:
    findings = []
    where = f"{target_name}:{entry.name}"
    try:
        closed = jax.make_jaxpr(entry.jfn)(*entry.args)
    except Exception as e:
        return [Finding("dtype", "DT002", where,
                        f"entry failed to trace for dtype analysis: {e!r}")]
    seen = set()
    for eqn in _iter_eqns(closed.jaxpr):
        if eqn.primitive.name == "convert_element_type":
            src = eqn.invars[0].aval.dtype
            dst = eqn.params["new_dtype"]
            s_i, d_i = _float_itemsize(src), _float_itemsize(dst)
            if s_i and d_i and d_i < s_i and d_i < compute_itemsize:
                key = (str(src), str(np.dtype(dst)))
                if key not in seen:
                    seen.add(key)
                    findings.append(Finding(
                        "dtype", "DT002", where,
                        f"float narrowing {src} -> {np.dtype(dst)} below "
                        f"the config compute dtype inside the compiled "
                        f"step"))
        for v in list(eqn.outvars) + list(eqn.invars):
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and np.dtype(dt) == np.float64:
                if "f64" not in seen:
                    seen.add("f64")
                    findings.append(Finding(
                        "dtype", "DT003", where,
                        f"float64 value inside the compiled step "
                        f"(primitive {eqn.primitive.name}) — x64 leaked "
                        f"into the hot path"))
    return findings


def _check_carry(target_name, entry) -> list:
    if entry.carry is None:
        return []
    in_argnum, out_index = entry.carry
    where = f"{target_name}:{entry.name}"
    try:
        out_shape = jax.eval_shape(entry.jfn, *entry.args)
    except Exception as e:
        return [Finding("dtype", "DT001", where,
                        f"entry failed eval_shape for carry check: {e!r}")]
    out_state = out_shape if out_index is None else out_shape[out_index]
    in_state = entry.args[in_argnum]
    in_leaves, in_tree = tree_flatten_with_path(in_state)
    out_leaves, out_tree = tree_flatten_with_path(out_state)
    if in_tree != out_tree:
        return [Finding(
            "dtype", "DT001", where,
            f"carried state changes pytree structure across the call "
            f"({in_tree} -> {out_tree}) — every structure variant is a "
            f"separate compiled program downstream")]
    findings = []
    for (path, a), (_, b) in zip(in_leaves, out_leaves):
        da, db = np.dtype(a.dtype), np.dtype(b.dtype)
        if da != db:
            findings.append(Finding(
                "dtype", "DT001", f"{where}:{keystr(path)}",
                f"carried state leaf drifts {da} -> {db}: the next step "
                f"sees a different dtype than this one was compiled for"))
        wa = bool(getattr(a, "weak_type", False))
        wb = bool(getattr(b, "weak_type", False))
        if wb and not wa:
            findings.append(Finding(
                "dtype", "DT003", f"{where}:{keystr(path)}",
                f"carried state leaf became weakly-typed across the call "
                f"— a Python scalar reached the carry; it will flip the "
                f"compile cache key on the next step"))
    return findings


def run(target, entries=None) -> list:
    entries = (target.engine.analysis_entries(target.params)
               if entries is None else entries)
    compute_itemsize = np.dtype(target.cfg.dtype).itemsize
    findings = []
    for entry in entries:
        findings.extend(_check_carry(target.name, entry))
        findings.extend(_walk_program(target.name, entry, compute_itemsize))
    return findings
