"""End-to-end driver: train a ~100M-param dense LM for a few hundred steps
with checkpoints, resume, and the straggler watchdog (deliverable b).

Default is a 300-step run on whatever devices exist (CPU included; pass
--steps 30 for a quick look). The config is qwen2-1.5b's family scaled to
~100M params.

Run:  PYTHONPATH=src python examples/train_100m.py --steps 300
"""
import argparse

from repro import compile_cache
from repro.configs.qwen2_1_5b import CONFIG
from repro.launch.train import train_loop

CFG_100M = CONFIG.replace(
    name="qwen2-100m",
    n_layers=8,
    d_model=512,
    n_heads=8,
    n_kv_heads=2,
    d_ff=2048,
    vocab=32000,
    head_dim=64,
)


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--ckpt-dir", default="/tmp/ckpt_100m")
    args = ap.parse_args()

    from repro.models.model import build_model, count_params_abstract
    n = count_params_abstract(build_model(CFG_100M))
    print(f"[100m] {n / 1e6:.1f}M params, {args.steps} steps, "
          f"batch {args.batch} x seq {args.seq}")
    out = train_loop(cfg=CFG_100M, steps=args.steps, batch=args.batch,
                     seq=args.seq, ckpt_dir=args.ckpt_dir, ckpt_every=50)
    print(f"[100m] loss {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f}; "
          f"{len(out['flagged'])} slow steps flagged")


if __name__ == "__main__":
    main()
