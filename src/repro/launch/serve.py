"""Batched serving launcher: continuous batching over the jitted
prefill/decode steps, on a mesh of the devices this process sees.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        [--reduced] [--requests 16] [--slots 4] [--max-seq 128]

Without ``--reduced`` the model runs at its published widths; ``--reduced``
is the CPU smoke size.
"""
import argparse
import logging
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from .. import configs
from ..configs.base import reduced as reduce_cfg
from ..models import build
from ..models.sharding import Rules
from ..serve import BatchSlots, ContinuousBatcher, Request
from .cache import use_compile_cache
from .mesh import make_local_mesh


class Server:
    """One model's weights, KV cache and jitted steps behind the
    `ContinuousBatcher` callbacks (`prefill`, `step`).  The cache is
    slot-major: slot ``i`` owns batch row ``i``.  Calls run under the
    server's mesh."""

    def __init__(self, cfg, par, mesh, slots: int, max_seq: int,
                 seed: int = 0):
        self.cfg, self.mesh = cfg, mesh
        self.slots, self.max_seq = slots, max_seq
        self.model = build(cfg, par)
        self.rules = Rules.make(mesh, par)
        model, rules = self.model, self.rules
        self._prefill = jax.jit(
            lambda p, b, c: model.prefill_fn(p, b, rules, c))
        self._decode = jax.jit(
            lambda p, b, c: model.decode_fn(p, b, c, rules))
        with mesh:
            self.params = model.init(jax.random.PRNGKey(seed))
            self.cache = model.init_cache(slots, max_seq)

    def prefill_logits(self, slot: int, prompt) -> jnp.ndarray:
        """Prefill ``prompt`` into ``slot``'s cache rows; the logits of its
        last position, (V,)."""
        B = self.slots
        # batch-shaped prefill with the prompt broadcast, then keep only
        # `slot`'s cache rows (cache leaves are (L, B, …): batch is dim 1)
        toks = jnp.broadcast_to(jnp.asarray(prompt)[None], (B, len(prompt)))
        batch = {"tokens": toks.astype(jnp.int32)}
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros((B, len(prompt), self.cfg.d_model))
        with self.mesh:
            logits, new_cache = self._prefill(self.params, batch, self.cache)

        def merge(new, old):
            sel = (jnp.arange(B) == slot).reshape(
                (1, B) + (1,) * (new.ndim - 2))
            return jnp.where(sel, new, old)

        self.cache = jax.tree.map(merge, new_cache, self.cache)
        return logits[slot, -1]

    def decode_logits(self, tokens, pos) -> jnp.ndarray:
        """One decode step for every slot: ``tokens`` (B, 1) written at
        position ``max(pos)``; the next-token logits, (B, V)."""
        batch = {"tokens": jnp.asarray(tokens, jnp.int32),
                 "pos": jnp.asarray(int(np.max(pos)), jnp.int32)}
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros((self.slots, 1, self.cfg.d_model))
        with self.mesh:
            logits, self.cache = self._decode(self.params, batch, self.cache)
        return logits[:, 0]

    def prefill(self, slot: int, prompt) -> int:
        return int(jnp.argmax(self.prefill_logits(slot, prompt)))

    def step(self, tokens, pos) -> np.ndarray:
        return np.asarray(jnp.argmax(self.decode_logits(tokens, pos), -1))

    def serve(self, requests: List[Request]) -> ContinuousBatcher:
        """Greedy-decode ``requests`` to completion; the drained batcher."""
        batcher = ContinuousBatcher(
            BatchSlots(capacity=self.slots, max_seq=self.max_seq),
            self.prefill, self.step)
        for r in requests:
            batcher.submit(r)
        batcher.run_until_drained()
        return batcher


def random_requests(n: int, vocab: int, max_new: int,
                    seed: int = 0) -> List[Request]:
    """``n`` requests with prompts of 4–23 random tokens and 2 to
    ``max_new`` − 1 new tokens each, drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    out = []
    for r in range(n):
        plen = int(rng.integers(4, 24))
        out.append(Request(r, rng.integers(0, vocab, plen).astype(np.int32),
                           max_new_tokens=int(rng.integers(2, max_new))))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.arch_names())
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-sized config (CPU)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=24)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    use_compile_cache()

    bundle = configs.get(args.arch)
    cfg = reduce_cfg(bundle.model) if args.reduced else bundle.model
    par = bundle.parallel_for("decode_32k", multi_pod=False)
    srv = Server(cfg, par, make_local_mesh(), args.slots, args.max_seq)
    reqs = random_requests(args.requests, cfg.vocab_size, args.max_new)
    t0 = time.time()
    batcher = srv.serve(reqs)
    dt = time.time() - t0
    total_tokens = sum(len(r.generated) for r in batcher.completed)
    print(f"served {len(batcher.completed)} requests, {total_tokens} tokens "
          f"in {dt:.1f}s ({total_tokens/dt:.1f} tok/s, {batcher.steps} "
          f"decode steps, avg batch occupancy "
          f"{batcher.slot_steps/max(batcher.steps,1):.2f}/{args.slots})")


if __name__ == "__main__":
    main()
