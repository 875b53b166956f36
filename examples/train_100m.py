"""End-to-end training driver: ~100M-param LM, a few hundred steps.

Uses the full substrate stack (data pipeline w/ prefetch, AdamW, remat,
checkpoint/restart driver).  Loss must decrease on the structured
synthetic stream.

    PYTHONPATH=src python examples/train_100m.py --steps 300
"""
import argparse
from dataclasses import replace

import jax

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import build
from repro.checkpoint import CheckpointStore
from repro.runtime import FaultTolerantDriver


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    args = ap.parse_args()

    # ~100M params: gemma3 family, scaled down
    cfg = replace(get_config("gemma3-12b"),
                  n_layers=10, d_model=640, n_heads=10, n_kv_heads=5,
                  head_dim=64, d_ff=2560, vocab=32768, window=32,
                  global_every=6, dtype="float32")
    print(f"model: {cfg.n_params / 1e6:.1f}M params")

    state, step, data = build(cfg, args.steps, lr=3e-3,
                              seq_len=args.seq_len, global_batch=args.batch)
    store = CheckpointStore("artifacts/ckpt/train100m", keep=2)
    driver = FaultTolerantDriver(step, store, data, ckpt_every=100)
    state, res = driver.run(state, args.steps)
    import numpy as np
    first, last = np.mean(res.losses[:10]), np.mean(res.losses[-10:])
    print(f"steps={res.steps_done} loss {first:.3f} -> {last:.3f}")
    assert last < first, "loss did not decrease!"
    print("OK: loss decreased")


if __name__ == "__main__":
    main()
