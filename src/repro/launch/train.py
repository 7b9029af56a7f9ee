"""Training launcher, on a mesh of the devices this process sees.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        [--steps N] [--ckpt DIR] [--reduced]

Without ``--reduced`` the model runs at its published widths; ``--reduced``
is the CPU smoke size.  On a fleet this binary runs per host under the
cluster scheduler (``--distributed``: jax.distributed.initialize picks up
the coordinator from env).
"""
import argparse
import logging

import jax

from .. import configs
from ..configs.base import reduced as reduce_cfg
from ..models import build
from ..models.sharding import Rules
from ..train.loop import train
from .cache import CACHE_DIR, use_compile_cache
from .mesh import make_local_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.arch_names())
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt", default=str(CACHE_DIR.parent / "ckpt"))
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-sized config (CPU)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize() (multi-host)")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    use_compile_cache()

    if args.distributed:
        jax.distributed.initialize()

    bundle = configs.get(args.arch)
    cfg = reduce_cfg(bundle.model) if args.reduced else bundle.model
    par = bundle.parallel_for("train_4k", multi_pod=False)
    if args.reduced:
        par = par.replace(num_microbatches=2, optimizer_state_dtype="float32",
                          grad_accum_dtype="float32")
    mesh = make_local_mesh()
    model = build(cfg, par)
    rules = Rules.make(mesh, par)
    with mesh:
        rep = train(model, rules, steps=args.steps, ckpt_dir=args.ckpt,
                    lr=args.lr)
    print(f"steps={rep.steps_run} final_loss={rep.final_loss:.4f} "
          f"preempted={rep.preempted} stragglers={len(rep.stragglers)}")


if __name__ == "__main__":
    main()
