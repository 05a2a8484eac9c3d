"""The weights the benchmark makes for a configuration, in any family.

The weights are the benchmark's own: made on the device from ``--seed`` in
one jitted call, in the program's parameter layout (the interface through
which ``SOIEngine`` takes weights), and read by the plain reference too, so
the reference takes nothing the program made. Values are rounded to
bfloat16, the published dtype, and held in the file's ``param_dtype``:
``float32`` as the program's own init holds them (the program casts them
to bfloat16 for compute), or ``bfloat16``. The family module
(:mod:`soibench.families`) gives the leaves; this module makes them.
"""

from __future__ import annotations

import numpy as np

from soibench import families


class ConfigMismatch(RuntimeError):
    """The program's config differs from the configuration file."""


def _listify(node):
    """Nodes whose keys are all whole numbers become lists, in order."""
    if not isinstance(node, dict):
        return node
    node = {k: _listify(v) for k, v in node.items()}
    if node and all(k.isdigit() for k in node):
        return [node[str(i)] for i in range(len(node))]
    return node


def _tree(flat: dict) -> dict:
    """Nest ``{"a/b/c": x}`` into the program's parameter pytree
    (``segments/0/...`` is the list ``segments``)."""
    root: dict = {}
    for path, val in flat.items():
        node = root
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    root = _listify(root)
    soi = root["soi"]
    if "fuse_mid" in soi:
        import jax.numpy as jnp
        soi["fuse"] = jnp.concatenate([soi.pop("fuse_mid"),
                                       soi.pop("fuse_skip")], axis=0)
    return root


def make_weights(config: dict, key):
    """The parameter pytree, made on the device from ``key`` in one jitted
    call."""
    import jax
    import jax.numpy as jnp
    specs = families.of(config).leaf_specs(config)
    dtype = jnp.dtype(config["param_dtype"])

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, shape, std, mean) in enumerate(specs):
            val = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32)
            val = val + (jnp.eye(shape[0], dtype=jnp.float32)
                         if mean == "eye" else mean)
            flat[path] = val.astype(jnp.bfloat16).astype(dtype)
        return _tree(flat)

    return make(key)


def weight_shapes(config: dict):
    """``ShapeDtypeStruct`` tree of :func:`make_weights` (nothing runs)."""
    import jax
    return jax.eval_shape(lambda k: make_weights(config, k),
                          jax.random.PRNGKey(0))


def seed_key(seed: int, stream: int):
    """A JAX key for ``stream`` of ``--seed`` (any size of whole number)."""
    import jax
    word = np.random.SeedSequence([int(seed) % 2 ** 64, stream]) \
        .generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def param_bytes(config: dict) -> int:
    import jax.numpy as jnp
    size = jnp.dtype(config["param_dtype"]).itemsize
    return int(sum(size * np.prod(shape) for _, shape, _, _ in
                   families.of(config).leaf_specs(config)))
