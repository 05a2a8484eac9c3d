"""Model families, found by name.

A configuration file names its family (``"family": "dense_gqa"``), and
``bench/soibench/families/<family>.py`` holds everything of the benchmark
that depends on the architecture. A new family arrives as a new module
here, with no edit to the generic harness. A module provides, as plain
functions of the configuration file's dict:

* ``sizes(config)``: the sizes the reference, the weights and the FLOP
  count follow; among them ``vocab``, ``stride``, ``mid`` (the SOI middle's
  ``(first, last)`` layers) and ``fp``, which the generic code reads;
* ``program_config(config)``: the program's ``ModelCfg`` that serves the
  file, checked field by field against it (``soibench.model.ConfigMismatch``
  where they differ);
* ``leaf_specs(config)``: ``(path, shape, std, mean)`` of every parameter,
  in the program's layout (:func:`soibench.model.make_weights`);
* ``hidden(w, s, tokens, quant)`` and ``head(w, s)``: the plain float32
  reference at the highest matmul precision, importing nothing of the
  program: final-norm hidden states of ``tokens`` and the LM head (d, vocab)
  (:mod:`soibench.reference`);
* ``token_flops(s, pos, head)``: operations the SOI schedule requires for
  the token at ``pos`` (:mod:`soibench.flops`);
* optionally ``KERNEL_COSTS``: ``{pallas_call name: cost(out, operands)}``
  of the kernels only this family's programs run, as
  :mod:`soibench.costs` gives them for the shared kernels.
"""

from __future__ import annotations

import importlib

from soibench import costs


def of(config: dict):
    """The family module the configuration file names."""
    name = config.get("family")
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"the configuration names no family module: "
                         f"{name!r}")
    return importlib.import_module(f"{__name__}.{name}")


def kernel_costs(family) -> dict:
    """Cost formulas of the kernels a cell of ``family`` runs: the shared
    ones of :mod:`soibench.costs` and the family's own ``KERNEL_COSTS``. A
    kernel has one formula; a second one for the same name is an error."""
    table = dict(costs.KERNELS)
    for kernel, fn in getattr(family, "KERNEL_COSTS", {}).items():
        if table.setdefault(kernel, fn) is not fn:
            raise ValueError(f"kernel {kernel} of {family.__name__} has a "
                             f"cost formula in soibench.costs already")
    return table
