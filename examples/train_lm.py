"""End-to-end driver: train a ~100M-parameter llama3-family model for a
few hundred steps with the production loop (sharded jit step, resumable
synthetic data, async checkpoints, straggler watchdog, auto-resume).

    PYTHONPATH=src python examples/train_lm.py --steps 300
"""

import argparse
import dataclasses

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import train


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    # ~100M-param llama3-family config (CPU-trainable)
    base = get_config("llama3.2-3b")
    cfg100m = dataclasses.replace(
        base,
        name="llama3-100m",
        num_layers=6,
        d_model=512,
        num_heads=8,
        num_kv_heads=4,
        head_dim=64,
        d_ff=1536,
        vocab_size=32000,
        tie_embeddings=True,
    )
    # register it so the launcher can find it
    import repro.configs as C

    C.ARCHS[cfg100m.name] = cfg100m

    losses = train(
        "llama3-100m",
        steps=args.steps,
        smoke=False,
        global_batch=4,
        seq_len=128,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=100,
        lr=3e-3,
    )
    print(f"loss: {losses[0]:.3f} → {losses[-1]:.3f} over {args.steps} steps")
    assert losses[-1] < losses[0], "training did not reduce the loss"


if __name__ == "__main__":
    main()
