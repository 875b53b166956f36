"""Training launcher — end-to-end driver (deliverable b).

Runs a real training loop for any ``--arch`` (reduced or full config) with
the complete substrate stack: synthetic data pipeline with prefetch,
AdamW, per-layer remat, checkpointing, fault-tolerant restart, straggler
monitoring.  On this CPU container use ``--reduced`` (the full configs are
exercised via the dry-run).

    python -m repro.launch.train --arch gemma3-12b --reduced --steps 200
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointStore
from repro.configs import ARCH_IDS, get_config
from repro.data import SyntheticLMData
from repro.models import LM
from repro.optim import adamw_init
from repro.runtime import FaultTolerantDriver, StragglerMonitor

from .compile_cache import enable_compile_cache
from .steps import make_train_step


def build(cfg, steps: int, lr: float, seq_len: int, global_batch: int):
    model = LM(cfg)
    data = SyntheticLMData(vocab=cfg.vocab, seq_len=seq_len,
                           global_batch=global_batch, seed=0)
    _, step_fn = make_train_step(cfg, mesh=None, seq_parallel=False,
                                 lr=lr, warmup=max(steps // 20, 5),
                                 total_steps=steps, loss_chunk=min(512, seq_len))
    jstep = jax.jit(step_fn, donate_argnums=(0,))

    def step(state, batch):
        b = {"ids": jnp.asarray(batch.ids), "labels": jnp.asarray(batch.labels),
             "mask": jnp.asarray(batch.mask)}
        if cfg.embeds_in:
            # stub modality frontend: embed tokens via the tied table
            b["embeds"] = jnp.take(state["params"]["embed"]["table"],
                                   b.pop("ids"), axis=0)
            b["labels"] = batch.labels
        if cfg.cross_attn_every:
            b["img_embeds"] = jnp.zeros(
                (batch.ids.shape[0], cfg.n_img_tokens, cfg.d_model),
                jnp.dtype(cfg.dtype))
        return jstep(state, b)

    params = model.init(jax.random.PRNGKey(0))
    state = {"params": params, "opt": adamw_init(params)}
    return state, step, data


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="gemma3-12b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"[train] arch={cfg.arch_id} N={cfg.n_params/1e6:.1f}M params "
          f"(reduced={args.reduced})")
    state, step, data = build(cfg, args.steps, args.lr, args.seq_len,
                              args.batch)
    store = CheckpointStore(f"{args.ckpt_dir}/{cfg.arch_id}", keep=2)
    driver = FaultTolerantDriver(step, store, data,
                                 ckpt_every=args.ckpt_every,
                                 straggler=StragglerMonitor())
    t0 = time.time()
    state, res = driver.run(state, args.steps)
    dt = time.time() - t0
    n_tok = args.steps * args.batch * args.seq_len
    first = np.mean(res.losses[:5]) if len(res.losses) >= 5 else res.losses[0]
    last = np.mean(res.losses[-5:])
    print(f"[train] {res.steps_done} steps in {dt:.1f}s "
          f"({n_tok / dt:.0f} tok/s), loss {first:.3f} -> {last:.3f}, "
          f"restarts={res.restarts}, stragglers={len(driver.straggler.flagged)}")
    assert last < first, "loss did not decrease"


if __name__ == "__main__":
    main()
