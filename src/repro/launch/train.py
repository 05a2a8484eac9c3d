"""End-to-end training driver.

Runs any ``--arch`` (full or smoke config) under the fault-tolerance
supervisor: host-sharded data, jitted train step, async atomic checkpoints,
restore-on-restart. ``--smoke`` (the reduced same-family config) is for the
CPU; the full configs, at their published widths, are for the chip.

Example (quickstart equivalent):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
from repro.obs.clock import now

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.data.pipeline import ShardedLMPipeline
from repro.distributed.fault_tolerance import SupervisorConfig, TrainSupervisor
from repro.distributed.sharding import split_axes
from repro.launch.compile_cache import use_compile_cache
from repro.launch.steps import make_train_step
from repro.models import transformer as T
from repro.optim import adamw_init


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=configs.ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--soi", default=None, choices=["pp", "fp"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import importlib
    mod = importlib.import_module(
        "repro.configs." + args.arch.replace("-", "_").replace(".", "_"))
    cfg = (mod.smoke_config(soi=args.soi) if args.smoke
           else mod.config(soi=args.soi))

    pipe = ShardedLMPipeline(global_batch=args.batch, seq_len=args.seq,
                             vocab=cfg.vocab, seed=args.seed,
                             host_id=jax.process_index(),
                             num_hosts=jax.process_count())

    params, _ = split_axes(T.init(jax.random.PRNGKey(args.seed), cfg))
    step_fn_inner = make_train_step(cfg, peak_lr=args.lr, warmup=20,
                                    total_steps=args.steps)
    jitted = jax.jit(step_fn_inner, donate_argnums=(0, 1))

    def extra_batch(b, s):
        extras = {}
        if cfg.frontend == "patch_stub":
            extras["patch_embeds"] = jnp.zeros(
                (b, cfg.frontend_len, cfg.d_model), jnp.bfloat16)
        if cfg.encoder is not None:
            extras["encoder_frames"] = 0.1 * jnp.ones(
                (b, cfg.encoder.n_frames, cfg.encoder.d_model), jnp.bfloat16)
        return extras

    losses = []

    def one_step(state, step):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch(step).items()}
        batch.update(extra_batch(args.batch, args.seq))
        p, o, metrics = jitted(state["params"], state["opt"], batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {loss:8.4f}  "
                  f"gnorm {float(metrics['grad_norm']):8.3f}  "
                  f"lr {float(metrics['lr']):.2e}", flush=True)
        return {"params": p, "opt": o}

    def make_state():
        p, _ = split_axes(T.init(jax.random.PRNGKey(args.seed), cfg))
        return {"params": p, "opt": adamw_init(p)}

    t0 = now()
    if args.ckpt_dir:
        sup = TrainSupervisor(
            SupervisorConfig(ckpt_dir=args.ckpt_dir,
                             ckpt_every=args.ckpt_every),
            make_state, one_step)
        state = sup.run(args.steps)
    else:
        state = make_state()
        state["params"] = params
        for step in range(args.steps):
            state = one_step(state, step)
    dt = now() - t0
    print(f"done: {args.steps} steps in {dt:.1f}s; "
          f"loss {losses[0]:.4f} -> {np.mean(losses[-10:]):.4f}")
    return losses


if __name__ == "__main__":
    main()
