"""End-to-end LM training driver: a ~100M-parameter granite-style model for a
few hundred steps on the synthetic pipeline, with checkpointing.

Defaults are CPU-sized (~20M params, 200 steps, ~15 min); pass ``--full`` for
the 100M-parameter configuration.

    PYTHONPATH=src python examples/train_lm.py [--full] [--steps 200]
"""
import argparse
import sys

from repro.configs.base import ArchConfig


def config_100m() -> ArchConfig:
    return ArchConfig(name="demo-100m", family="dense", n_layers=12,
                      d_model=768, n_heads=12, n_kv_heads=4, head_dim=64,
                      d_ff=2048, vocab=8192, dtype="float32")


def config_20m() -> ArchConfig:
    return ArchConfig(name="demo-20m", family="dense", n_layers=6,
                      d_model=384, n_heads=6, n_kv_heads=2, head_dim=64,
                      d_ff=1024, vocab=4096, dtype="float32")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="~100M params")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = config_100m() if args.full else config_20m()
    print(f"[example] {cfg.name}: {cfg.param_count()/1e6:.1f}M params")

    # monkey-patch the launcher's arch lookup so the demo config flows
    # through the exact production code path (launch/train.py)
    import repro.launch.train as lt
    lt.get_arch = lambda _: cfg
    rc = lt.main(["--arch", cfg.name, "--steps", str(args.steps),
                  "--batch", str(args.batch), "--seq", str(args.seq),
                  "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "50",
                  "--lr", "1e-3", "--log-every", "20"])
    sys.exit(rc)


if __name__ == "__main__":
    main()
