"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch rwkv6-1.6b --reduced \\
      --steps 50 --checkpoint-dir /tmp/ckpt

Full-size configs target the production mesh (run under the dry-run's
XLA_FLAGS on a real pod slice); ``--reduced`` shrinks the architecture for
CPU-scale end-to-end runs (the "train a ~100M model for a few hundred
steps" driver uses this path — see examples/train_lm.py).
"""

from __future__ import annotations

import argparse
import logging

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.dist.sharding import make_sharder
from repro.launch.mesh import make_production_mesh, make_test_mesh
from repro.models.lm import build_model
from repro.testing import reduced_config
from repro.train.loop import TrainLoopConfig, train


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["none", "test", "pod", "multipod"],
                    default="none")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    shape = ShapeSpec("cli_train", args.seq, args.batch, "train")
    mesh = None
    if args.mesh == "test":
        n = len(jax.devices())
        mesh = make_test_mesh((1, n), ("data", "model"))
    elif args.mesh == "pod":
        mesh = make_production_mesh()
    elif args.mesh == "multipod":
        mesh = make_production_mesh(multi_pod=True)
    sharder = make_sharder(cfg, mesh, "train")
    loop_cfg = TrainLoopConfig(
        total_steps=args.steps,
        checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir,
    )
    ctx = mesh if mesh is not None else _nullcontext()
    with ctx:
        state, history = train(model, shape, sharder, loop_cfg)
    print(f"final loss: {history[-1]['loss']:.4f} after {len(history)} steps")


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
