"""End-to-end LM training on CPU: ~100M-class reduced qwen3 config, a few

hundred steps on the deterministic synthetic pipeline, with checkpointing,
a mid-run SIMULATED FAILURE (restored automatically), and the paper's
technique enabled at the lm_head (precision policy).

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""

import argparse
import tempfile

import numpy as np

from repro.configs.base import RunConfig
from repro.launch.train import train
from repro.runtime import compile_cache


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--arch", default="qwen3-0.6b")
    args = ap.parse_args()

    run_cfg = RunConfig(
        learning_rate=1e-3, warmup_steps=20, total_steps=args.steps,
        optimizer="adamw_dd",          # df32 master weights: paper's engine
        policy={},                     # set {"lm_head": "dd"} for dd logits
    )
    with tempfile.TemporaryDirectory() as ckpt_dir:
        out = train(args.arch, steps=args.steps, batch=args.batch,
                    seq=args.seq, reduce=True, ckpt_dir=ckpt_dir,
                    run_cfg=run_cfg, log_every=20,
                    inject_failure_at=args.steps // 2)
    losses = out["losses"]
    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"\nfailures recovered: {out['failures']}")
    print(f"loss {first:.3f} -> {last:.3f} ({(1 - last / first) * 100:.0f}% down)")
    assert last < first, "loss must decrease"


if __name__ == "__main__":
    main()
