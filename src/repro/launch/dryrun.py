import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()
# The two lines above MUST run before any jax import (jax locks the device
# count at first init).  Do not move them.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell with
ShapeDtypeStruct inputs (no allocation), record memory/cost/collective
statistics per cell into an incremental JSON cache.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun [--arch ID …] [--cell C …]
        [--mesh single|multi|both] [--out results/dryrun] [--force]
"""
import argparse
import json
import pathlib
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs
from ..configs.base import SHAPE_CELLS, ShapeCell
from ..models import build
from ..models.sharding import Rules
from ..train.step import (make_abstract_train_state, make_train_state_specs,
                          make_train_step)
from . import hlo_stats
from .cache import use_compile_cache
from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh


def skip_reason(arch: str, cell: ShapeCell) -> Optional[str]:
    cfg = configs.get(arch).model
    if cell.name == "long_500k" and not cfg.supports_long_context:
        return ("full-attention arch: one decode step against a 512k dense KV "
                "cache is quadratic-history; sub-quadratic families only "
                "(DESIGN.md §Arch-applicability)")
    return None


def model_flops(arch: str, cell: ShapeCell) -> float:
    cfg = configs.get(arch).model
    n_active = cfg.active_param_count()
    if cell.kind == "train":
        return 6.0 * n_active * cell.global_batch * cell.seq_len
    if cell.kind == "prefill":
        return 2.0 * n_active * cell.global_batch * cell.seq_len
    return 2.0 * n_active * cell.global_batch          # decode: 1 token/seq


def batch_abstract(cfg, cell: ShapeCell, mode: str):
    B, S = cell.global_batch, cell.seq_len
    if mode == "train" or mode == "prefill":
        out = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if cfg.family == "encdec":
            out["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
    else:
        out = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
               "pos": jax.ShapeDtypeStruct((), jnp.int32)}
        if cfg.family == "encdec":
            out["frames"] = jax.ShapeDtypeStruct((B, 1, cfg.d_model), jnp.bfloat16)
    return out


def lower_cell(arch: str, cell: ShapeCell, mesh, multi_pod: bool):
    bundle = configs.get(arch)
    cfg = bundle.model
    par = bundle.parallel_for(cell.name, multi_pod)
    rules = Rules.make(mesh, par)
    model = build(cfg, par)
    named = lambda spec: jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec,
        is_leaf=lambda x: isinstance(x, P))

    if cell.kind == "train":
        bundle_t = make_train_step(model, rules)
        state = {"params": model.abstract_params(),
                 "opt": make_abstract_train_state(model)["opt"]}
        batch = batch_abstract(cfg, cell, "train")
        bspec = bundle_t.batch_spec(batch)
        metric_specs = {"loss": P(), "grad_norm": P(), "step": P()}
        fn = jax.jit(
            bundle_t.step_fn,
            in_shardings=(named(bundle_t.state_specs), named(bspec)),
            out_shardings=(named(bundle_t.state_specs), named(metric_specs)),
            donate_argnums=(0,))
        return fn.lower(state, batch), rules

    # serving
    pspecs = model.param_specs(rules)
    params = model.abstract_params()
    cache = model.abstract_cache(cell.global_batch, cell.seq_len)
    cspecs = model.cache_specs(cell.global_batch, cell.seq_len, rules)
    if cell.kind == "prefill":
        batch = batch_abstract(cfg, cell, "prefill")
        names = {"tokens": ("batch", "seq"), "frames": ("batch", "seq", None)}
        bspec = {k: rules.spec(v.shape, names[k][:len(v.shape)])
                 for k, v in batch.items()}
        logits_spec = rules.spec((cell.global_batch, 1, cfg.padded_vocab()),
                                 ("batch", None, "vocab_act"))
        fn = jax.jit(
            lambda p, b, c: model.prefill_fn(p, b, rules, c),
            in_shardings=(named(pspecs), named(bspec), named(cspecs)),
            out_shardings=(NamedSharding(mesh, logits_spec), named(cspecs)),
            donate_argnums=(2,))
        return fn.lower(params, batch, cache), rules

    batch = batch_abstract(cfg, cell, "decode")
    names = {"tokens": ("batch", "seq"), "frames": ("batch", "seq", None),
             "pos": ()}
    bspec = {k: rules.spec(v.shape, names[k][:len(v.shape)])
             for k, v in batch.items()}
    logits_spec = rules.spec((cell.global_batch, 1, cfg.padded_vocab()),
                             ("batch", None, "vocab_act"))
    fn = jax.jit(
        lambda p, b, c: model.decode_fn(p, b, c, rules),
        in_shardings=(named(pspecs), named(bspec), named(cspecs)),
        out_shardings=(NamedSharding(mesh, logits_spec), named(cspecs)),
        donate_argnums=(2,))
    return fn.lower(params, batch, cache), rules


def run_cell(arch: str, cell_name: str, multi_pod: bool, out_dir: pathlib.Path,
             force: bool = False) -> Dict:
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    out_path = out_dir / f"{arch}__{cell_name}__{mesh_tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    cell = SHAPE_CELLS[cell_name]
    rec: Dict = {"arch": arch, "cell": cell_name, "mesh": mesh_tag,
                 "timestamp": time.time()}
    reason = skip_reason(arch, cell)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        out_path.write_text(json.dumps(rec, indent=2))
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = 512 if multi_pod else 256
        t0 = time.time()
        lowered, rules = lower_cell(arch, cell, mesh, multi_pod)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        txt = compiled.as_text()
        st = hlo_stats.analyze(txt)
        print(mem)
        print({k: ca.get(k) for k in ("flops", "bytes accessed")})

        mf = model_flops(arch, SHAPE_CELLS[cell_name])
        compute_t = st.flops / PEAK_FLOPS_BF16
        memory_t = st.bytes / HBM_BW
        coll_t = st.collective_bytes / ICI_BW
        dominant = max((("compute", compute_t), ("memory", memory_t),
                        ("collective", coll_t)), key=lambda kv: kv[1])[0]
        rec.update({
            "status": "ok",
            "chips": chips,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_bytes_per_device": (mem.argument_size_in_bytes
                                          + mem.temp_size_in_bytes
                                          + mem.output_size_in_bytes
                                          - mem.alias_size_in_bytes),
            },
            "xla_cost_analysis": {k: ca.get(k) for k in ("flops", "bytes accessed")},
            "hlo": st.as_dict(),
            "model_flops_global": mf,
            "model_flops_per_device": mf / chips,
            "roofline": {
                "compute_s": compute_t,
                "memory_s": memory_t,
                "collective_s": coll_t,
                "dominant": dominant,
                "useful_flops_ratio": (mf / chips) / st.flops if st.flops else None,
            },
            "sharding_fallbacks": rules.dropped,
        })
    except Exception as e:  # record failures — they are dry-run bugs to fix
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=None)
    ap.add_argument("--cell", nargs="*", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="both")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = args.arch or configs.arch_names()
    cells = args.cell or list(SHAPE_CELLS)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    for arch in archs:
        for cell in cells:
            for mp in meshes:
                tag = "2x16x16" if mp else "16x16"
                t0 = time.time()
                rec = run_cell(arch, cell, mp, out_dir, args.force)
                status = rec.get("status")
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f"dom={r['dominant']} comp={r['compute_s']:.3e}s "
                             f"mem={r['memory_s']:.3e}s coll={r['collective_s']:.3e}s "
                             f"hbm={rec['memory']['peak_bytes_per_device']/2**30:.2f}GiB")
                elif status == "error":
                    extra = rec.get("error", "")[:160]
                print(f"[{arch} × {cell} × {tag}] {status} "
                      f"({time.time()-t0:.0f}s) {extra}", flush=True)


if __name__ == "__main__":
    main()
