"""End-to-end training driver: train a ~100M-param qwen2.5-family model for
a few hundred steps on CPU, with checkpoint/restart.

    PYTHONPATH=src python examples/train_e2e.py [--steps 200] [--d-model 256]

A crash mid-run resumes from the last atomic checkpoint:
    PYTHONPATH=src python examples/train_e2e.py --resume
"""
import argparse
import dataclasses
from pathlib import Path

from repro import configs
from repro.launch.train import run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--ckpt", default="/tmp/repro_train_e2e")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    # ~100M-param decoder-only (qwen family: GQA + qkv bias + SwiGLU).
    n_heads = max(args.d_model // 64, 2)
    cfg = dataclasses.replace(
        configs.get("qwen2.5-3b"),
        n_layers=args.layers, d_model=args.d_model,
        n_heads=n_heads, n_kv_heads=2 if n_heads % 2 == 0 else 1,
        d_ff=args.d_model * 4, vocab=args.vocab, head_dim=64,
        remat="none", fsdp=False, dtype="float32")
    from repro.models import build
    n = build(cfg).param_count()
    print(f"model: {n/1e6:.1f}M params, {args.layers}L d{args.d_model}")

    out = run(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
              ckpt_dir=args.ckpt, ckpt_every=50, log_every=10,
              resume=args.resume, lr=1e-3)
    first = sum(out["losses"][:10]) / min(len(out["losses"]), 10)
    last = sum(out["losses"][-10:]) / min(len(out["losses"]), 10)
    print(f"\nloss {first:.3f} -> {last:.3f} over {args.steps} steps "
          f"({out['wall_s']:.0f}s)")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable(Path(__file__).resolve().parents[1])
    main()
