"""End-to-end LM training driver example.

Runs the full production path on this container: config -> init -> jitted
train_step (remat, microbatch accumulation, AdamW + cosine schedule) ->
deterministic data pipeline -> async checkpointing -> crash + bit-exact
resume (simulated kill halfway).

Defaults are CPU-sized (a ~3M-param LM, 60 steps). `--preset lm-100m
--steps 300` is the full-fat configuration for real hardware; identical
code path.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 60]
"""
import argparse
import shutil
import tempfile

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import PRESETS, run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="lm-tiny")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()
    cfg = PRESETS[args.preset]
    ckpt = tempfile.mkdtemp(prefix="trainlm_")
    try:
        print(f"== phase 1: train to step {args.steps // 2} then 'crash' ==")
        run(cfg, args.steps // 2, args.batch, args.seq, ckpt_dir=ckpt,
            microbatches=2)
        print("\n== phase 2: resume from checkpoint, finish ==")
        state, hist = run(cfg, args.steps, args.batch, args.seq,
                          ckpt_dir=ckpt, microbatches=2, resume=True)
        print(f"\nloss: first {hist[0]:.3f} -> last {hist[-1]:.3f}")
        assert hist[-1] < hist[0], "loss should decrease"
        print("OK — trained, crashed, resumed, improved")
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)


if __name__ == "__main__":
    enable_compile_cache()
    main()
