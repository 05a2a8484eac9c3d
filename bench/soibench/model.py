"""A configuration file, the program's config that serves it, and the
weights the benchmark makes for both.

The weights are the benchmark's own: made on the device from ``--seed`` in
one jitted call, in the program's parameter layout (the interface through
which ``SOIEngine`` takes weights), and read by the plain reference too, so
the reference takes nothing the program made. Values are rounded to
bfloat16, the published dtype, and held in float32, as the program's own
init holds them; the program casts them to bfloat16 for compute.
"""

from __future__ import annotations

import dataclasses
import importlib

import numpy as np


class ConfigMismatch(RuntimeError):
    """The program's config differs from the configuration file."""


def sizes(config: dict) -> dict:
    """The sizes the reference and the weights follow, from the file."""
    soi = config["soi"]
    return {
        "d": config["hidden_size"], "ff": config["intermediate_size"],
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv": config["num_key_value_heads"], "dh": config["head_dim"],
        "vocab": config["vocab_size"], "tied": config["tie_word_embeddings"],
        "eps": config["rms_norm_eps"], "theta": float(config["rope_theta"]),
        "window": config.get("sliding_window"),
        "qk_norm": bool(config.get("qk_norm")),
        "stride": soi["stride"], "mid": (soi["first_layer"],
                                         soi["last_layer"]),
        "fp": soi["mode"] == "fp",
    }


def program_config(config: dict):
    """The program's ``ModelCfg`` for this file, checked field by field
    against the file's sizes: the config as run is the one the file
    states."""
    prog = config["program"]
    mod = importlib.import_module(
        "repro.configs." + prog["arch"].replace("-", "_").replace(".", "_"))
    # "smoke": the program's reduced same-family config, for the CPU tests
    make = mod.smoke_config if prog.get("smoke") else mod.config
    # the program computes in the dtype the file states
    cfg = dataclasses.replace(make(soi=prog["soi"]),
                              dtype=config["compute_dtype"])
    s = sizes(config)
    (seg,) = cfg.segments
    (blk,) = seg.blocks
    a = blk.attn
    got = {
        "d": cfg.d_model, "ff": blk.mlp.d_ff, "layers": cfg.n_layers,
        "heads": a.n_heads, "kv": a.n_kv, "dh": a.head_dim,
        "vocab": cfg.vocab, "tied": cfg.tie_embeddings, "eps": cfg.norm_eps,
        "theta": float(a.rope_theta), "window": a.window,
        "qk_norm": a.qk_norm, "stride": cfg.soi.stride,
        "mid": (cfg.soi.first_layer, cfg.soi.last_layer),
        "fp": cfg.soi.mode == "fp",
    }
    diff = {k: (s[k], got[k]) for k in s if s[k] != got[k]}
    if (diff or blk.mlp.kind != "swiglu" or blk.norm != "rmsnorm"
            or a.kind != "gqa" or a.softmax_scale is not None
            or a.logit_softcap is not None or a.rope_pct != 1.0
            or cfg.logits_softcap or cfg.embed_scale
            or cfg.soi.extrapolation != config["soi"]["extrapolation"]):
        raise ConfigMismatch(f"program config {cfg.name} departs from the "
                             f"configuration file: {diff}")
    return cfg


def leaf_specs(config: dict) -> list:
    """(path, shape, std, mean) of every parameter, in the program's
    layout: stacked layers under ``segments[0]["sub0"]``, RMSNorm scales in
    the (1 + scale) convention. ``mean`` is a number or "eye" (the identity
    matrix).

    Logits spread with a standard deviation of about 4. With a tied head
    the embedding rows have norm 1 and the final norm's weight is about 4,
    so a token's own embedding is a small part of the residual stream and
    the model does not merely repeat its input token."""
    s = sizes(config)
    d, ff, n, h, kv, dh, v = (s["d"], s["ff"], s["layers"], s["heads"],
                              s["kv"], s["dh"], s["vocab"])
    blk = "segments/0/sub0/"
    out = [
        ("embed", (v, d), d ** -0.5 if s["tied"] else 1.0, 0.0),
        ("final_norm/scale", (d,), 0.1, 3.0 if s["tied"] else 0.0),
        (blk + "ln1/scale", (n, d), 0.1, 0.0),
        (blk + "ln2/scale", (n, d), 0.1, 0.0),
        (blk + "attn/wq", (n, d, h, dh), d ** -0.5, 0.0),
        (blk + "attn/wk", (n, d, kv, dh), d ** -0.5, 0.0),
        (blk + "attn/wv", (n, d, kv, dh), d ** -0.5, 0.0),
        (blk + "attn/wo", (n, h, dh, d), (h * dh) ** -0.5, 0.0),
        (blk + "mlp/up", (n, d, ff), d ** -0.5, 0.0),
        (blk + "mlp/gate", (n, d, ff), d ** -0.5, 0.0),
        (blk + "mlp/down", (n, ff, d), ff ** -0.5, 0.0),
        ("soi/compress", (s["stride"], d, d), (s["stride"] * d) ** -0.5,
         0.0),
        # fuse = [middle; skip] -> d: the middle's share is as large as the
        # skip's, whose part is the identity plus noise
        ("soi/fuse_mid", (d, d), d ** -0.5, 0.0),
        ("soi/fuse_skip", (d, d), 0.02, "eye"),
    ]
    if s["qk_norm"]:
        out += [(blk + "attn/q_norm/scale", (n, dh), 0.1, 0.0),
                (blk + "attn/k_norm/scale", (n, dh), 0.1, 0.0)]
    if not s["tied"]:
        out.append(("lm_head", (d, v), 4.0 / np.sqrt(d), 0.0))
    return out


def _tree(flat: dict) -> dict:
    """Nest ``{"a/b/c": x}`` into the program's parameter pytree."""
    root: dict = {}
    for path, val in flat.items():
        node = root
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    root["segments"] = [root["segments"]["0"]]
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
    specs = leaf_specs(config)

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, shape, std, mean) in enumerate(specs):
            val = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32)
            val = val + (jnp.eye(shape[0], dtype=jnp.float32)
                         if mean == "eye" else mean)
            flat[path] = val.astype(jnp.bfloat16).astype(jnp.float32)
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
    return int(sum(4 * np.prod(shape) for _, shape, _, _ in
                   leaf_specs(config)))
