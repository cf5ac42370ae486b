"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs a real (CPU-feasible) training job on a reduced config by default, or
lowers the full config when --dry-run is given.  Wires together: config ->
model -> data pipeline -> pjit train step -> checkpointing -> fault-tolerant
loop (restart, straggler policy), i.e. the full production path at toy scale.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.configs import get_config, reduced
from repro.data.pipeline import DataConfig, ShardedLoader, SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import build_model
from repro.runtime.trainer import (
    TrainLoopConfig, make_train_step, train_loop,
)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (assignment) config instead of reduced")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = reduced(cfg).replace(grad_accum=1)
    if cfg.train_act_shard:
        cfg = cfg.replace(act_shard=cfg.train_act_shard)
    model = build_model(cfg)

    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch, seed=args.seed))
    loader = ShardedLoader(data)

    def data_iter(step):
        b = loader(step)
        batch = {k: jax.numpy.asarray(v) for k, v in b.items()}
        if cfg.family == "vlm":
            import jax.numpy as jnp
            P = min(cfg.n_patch_tokens, args.seq // 4)
            batch["vis_embeds"] = jnp.zeros((args.batch, P, cfg.d_model),
                                            jnp.bfloat16)
            batch["pos_ids"] = jnp.broadcast_to(
                jnp.arange(args.seq)[None, :, None],
                (args.batch, args.seq, 3)).astype(jnp.int32)
        if cfg.family == "audio":
            import jax.numpy as jnp
            batch["frames"] = jnp.zeros((args.batch, cfg.enc_frames,
                                         cfg.d_model), jnp.bfloat16)
        return batch

    step_fn = jax.jit(make_train_step(model, None, peak_lr=args.lr,
                                      total_steps=args.steps,
                                      warmup_steps=max(1, args.steps // 10)))
    loop_cfg = TrainLoopConfig(total_steps=args.steps,
                               log_every=args.log_every,
                               ckpt_every=args.ckpt_every,
                               ckpt_dir=args.ckpt_dir)
    t0 = time.time()
    state, history = train_loop(model, data_iter, loop_cfg,
                                key=jax.random.PRNGKey(args.seed),
                                step_fn=step_fn,
                                on_metrics=lambda m: print(json.dumps(m)))
    dt = time.time() - t0
    print(f"[train] {args.arch}: {args.steps} steps in {dt:.1f}s "
          f"(first loss {history[0]['loss']:.3f} -> last "
          f"{history[-1]['loss']:.3f})")
    loader.close()
    return history


if __name__ == "__main__":
    main()
