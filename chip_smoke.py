#!/usr/bin/env python3
"""Smoke run of the main path on a TPU, through the entry points a user calls.

    python chip_smoke.py               # one chip: compiler, validate, serve
    python chip_smoke.py --four-chips  # four chips: the planned pipeline ring

One chip runs three phases, one line each:

* compiler — spec → analyze().classify().fifoize().size().plan() →
  compile(backend="pallas") for jacobi-1d, jacobi-2d and heat-3d at their
  full sizes, plus jacobi-2d forced to the addressable mode; each output is
  checked against the program's jitted jnp reference on the chip;
* validate — `validate(backend="pallas")` on jacobi-2d: every planned
  channel trace replayed by the SMEM ring kernel, both directions;
* serve — smollm-135m at its published widths through
  `repro.launch.serve.Server`: one request served alone is checked against
  `Model.forward` in float32 without a cache (prefill plus two greedy
  tokens), then four requests are served by the continuous batcher.

``--four-chips`` runs only the pipeline ring of `repro.comm.pipeline` over
four stages: the planner-selected lowering, the same ring forced to the
reorder buffer, and the non-pipelined reference loss and gradients.

Any fault ends the run with a traceback and a non-zero exit, as does a
machine without a TPU.  The last line is ``{"ok": true, "device": ...}``.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.core.polybench  # noqa: E402,F401  (populate the kernel registry)
from repro.core.analysis import analyze  # noqa: E402
from repro.core.registry import get  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402

#: (kernel, shape, T, block, mode); mode None is the one the plan selects
COMPILER_CASES = (
    ("jacobi-1d", (1 << 20,), 128, 128, None),
    ("jacobi-2d", (1024, 1024), 64, 8, None),
    ("heat-3d", (128, 128, 128), 16, 8, None),
    ("jacobi-2d", (1024, 1024), 4, 8, "addressable"),
)

#: f32 kernels against the f32 XLA reference: the same formula in the same
#: order, so only rounding of a few ulps per step separates them
KERNEL_TOL = dict(rtol=1e-5, atol=1e-5)

#: served (bf16 weights and activations) against the float32 forward: the
#: largest logit difference may be this share of the largest |logit|
LOGIT_TOL = 0.05


def _line(phase: str, **fields) -> None:
    print(f"phase={phase} " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def compiler_phase(cases=COMPILER_CASES, seed: int = 0) -> None:
    rng = np.random.default_rng(seed)
    for name, shape, steps, block, mode in cases:
        t0 = time.perf_counter()
        c = (analyze(get(name)).classify().fifoize().size().plan()
             .compile(backend="pallas", mode=mode))
        if mode is None and c.mode != "fifo-ring":
            raise AssertionError(f"{name}: plan selected {c.mode}")
        x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        kernel = jax.jit(lambda a: c(a, steps, block)).lower(x).compile()
        compile_s = time.perf_counter() - t0
        if "tpu_custom_call" not in kernel.as_text():
            raise AssertionError(f"{name}: no Pallas kernel in the program")
        t0 = time.perf_counter()
        got = kernel(x).block_until_ready()
        run_s = time.perf_counter() - t0
        want = jax.jit(lambda a: c.program.ref(a, steps))(x)
        err = float(jnp.max(jnp.abs(got - want)))
        if not bool(jnp.allclose(got, want, **KERNEL_TOL)):
            raise AssertionError(f"{name} {c.mode}: max |err| {err}")
        _line("compiler", kernel=name, shape="x".join(map(str, shape)),
              T=steps, b=block, mode=c.mode, max_abs_err=f"{err:.3e}",
              compile_s=f"{compile_s:.2f}", run_s=f"{run_s:.4f}")


def validate_phase(name: str = "jacobi-2d") -> None:
    t0 = time.perf_counter()
    v = (analyze(get(name)).classify().fifoize().size().plan()
         .validate(backend="pallas").validation)
    if v.backend != "pallas" or v.replays < 1:
        raise AssertionError(f"{name}: nothing replayed ({v})")
    _line("validate", kernel=name, backend=v.backend, channels=len(v.channels),
          replays=v.replays, rejections=v.rejections,
          seconds=f"{time.perf_counter() - t0:.2f}")


def serve_phase(cfg, par, slots: int = 4, max_seq: int = 64,
                requests: int = 4, max_new: int = 17, seed: int = 0) -> None:
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import Server, random_requests

    t0 = time.perf_counter()
    srv = Server(cfg, par, make_local_mesh(), slots, max_seq, seed)
    V = cfg.vocab_size
    prompt = np.random.default_rng(seed).integers(0, V, 12).astype(np.int32)

    # one request alone in slot 0: prefill, then two greedy decode steps
    served = [srv.prefill_logits(0, prompt)]
    toks = [int(jnp.argmax(served[0]))]
    tokens, pos = np.zeros((slots, 1), np.int32), np.zeros(slots, np.int32)
    for i in range(2):
        tokens[0, 0], pos[0] = toks[-1], len(prompt) + i
        served.append(srv.decode_logits(tokens, pos)[0])
        toks.append(int(jnp.argmax(served[-1])))
    served = np.stack([np.asarray(s, np.float32)[:V] for s in served])

    # the reference: Model.forward over the prompt and the first two served
    # tokens, no cache, float32 weights and matmuls
    seq = jnp.asarray(np.concatenate([prompt, toks[:2]])[None], jnp.int32)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), srv.params)
    with jax.default_matmul_precision("highest"), srv.mesh:
        ref = jax.jit(lambda p, t: srv.model.forward(
            p, {"tokens": t}, srv.rules, "train")[0])(p32, seq)
    ref = np.asarray(ref[0, len(prompt) - 1:, :V], np.float32)
    setup_s = time.perf_counter() - t0
    if not (np.isfinite(served).all() and np.isfinite(ref).all()):
        raise AssertionError("non-finite logits")
    err = float(np.max(np.abs(served - ref)))
    scale = float(np.max(np.abs(ref)))
    if err > LOGIT_TOL * scale:
        raise AssertionError(f"served logits differ from the f32 forward by "
                             f"{err} (largest |logit| {scale})")
    # greedy: each served token is a top token of the reference, up to the
    # difference the logits may have
    for i, t in enumerate(toks):
        if ref[i, t] < ref[i].max() - 2 * err:
            raise AssertionError(f"token {i}: served {t}, reference top "
                                 f"{int(ref[i].argmax())}")

    reqs = random_requests(requests, V, max_new, seed)
    t0 = time.perf_counter()
    batcher = srv.serve(reqs)
    serve_s = time.perf_counter() - t0
    done = batcher.completed
    if len(done) != requests or any(
            len(r.generated) != r.max_new_tokens for r in done):
        raise AssertionError(f"served {[len(r.generated) for r in done]} of "
                             f"{[r.max_new_tokens for r in reqs]} tokens")
    if any(not 0 <= t < V for r in done for t in r.generated):
        raise AssertionError("token id outside the vocabulary")
    _line("serve", arch=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
          vocab=V, params=sum(a.size for a in jax.tree.leaves(srv.params)),
          logit_max_abs_err=f"{err:.4f}", logit_scale=f"{scale:.4f}",
          greedy_tokens=toks, requests=len(done),
          tokens=sum(len(r.generated) for r in done),
          decode_steps=batcher.steps, setup_s=f"{setup_s:.2f}",
          serve_s=f"{serve_s:.2f}")


def pipeline_phase(devices, stages: int = 4, microbatches: int = 8,
                   mb: int = 16, width: int = 512, lr: float = 0.5) -> None:
    from jax.sharding import Mesh

    from repro.comm import PipelineSpec, analyze_pipeline
    from repro.comm.pipeline import pipeline_train_step, ring_lowering
    from repro.runtime.lowering import FIFO_STREAM, REORDER_BUFFER

    if len(devices) < stages:
        raise SystemExit(f"{stages} pipeline stages need {stages} devices, "
                         f"found {len(devices)}")
    mesh = Mesh(np.array(devices[:stages]), ("pipe",))
    _, plans = analyze_pipeline(PipelineSpec(stages=stages,
                                             microbatches=microbatches))
    if ring_lowering(plans) != FIFO_STREAM:
        raise AssertionError(f"planner chose {ring_lowering(plans)}")

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    def loss_head(h, tgt):
        return jnp.mean((h - tgt) ** 2)

    def ref_loss(params, xs, tgt):
        def one(x, t):
            for s in range(stages):
                x = stage_fn(jax.tree.map(lambda a: a[s], params), x)
            return loss_head(x, t)
        return jnp.mean(jax.vmap(one)(xs, tgt))

    k = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {"w": jax.random.normal(k[0], (stages, width, width))
              / np.sqrt(width),
              "b": jnp.zeros((stages, width))}
    xs = jax.random.normal(k[1], (microbatches, mb, width))
    tgt = 0.1 * jax.random.normal(k[2], (microbatches, mb, width))
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(ref_loss))(params, xs, tgt)
        # the planned ring moves activations by collective-permute alone;
        # the reorder buffer all-gathers them instead
        for label, kw, collective, absent in (
                ("planned", {"plans": plans}, "collective-permute",
                 "all-gather"),
                ("reorder-buffer", {"lowering": REORDER_BUFFER},
                 "all-gather", "collective-permute")):
            t0 = time.perf_counter()
            step = pipeline_train_step(stage_fn, loss_head, mesh, "pipe",
                                       lr=lr, **kw)
            compiled = step.lower(params, xs, tgt).compile()
            compile_s = time.perf_counter() - t0
            hlo = compiled.as_text()
            if collective not in hlo or absent in hlo:
                raise AssertionError(f"{label}: step should hold "
                                     f"{collective} and no {absent}")
            t0 = time.perf_counter()
            new, loss = jax.block_until_ready(compiled(params, xs, tgt))
            run_s = time.perf_counter() - t0
            grads = jax.tree.map(lambda p, n: (p - n) / lr, params, new)
            loss_err = abs(float(loss) - float(want))
            grad_err = max(float(jnp.max(jnp.abs(g - w))) for g, w in zip(
                jax.tree.leaves(grads), jax.tree.leaves(want_g)))
            grad_scale = max(float(jnp.max(jnp.abs(w)))
                             for w in jax.tree.leaves(want_g))
            if loss_err > 1e-5 * abs(float(want)) or \
                    grad_err > 1e-4 * grad_scale:
                raise AssertionError(f"{label}: loss err {loss_err}, grad "
                                     f"err {grad_err} (scale {grad_scale})")
            _line("pipeline", ring=label, stages=stages,
                  microbatches=microbatches, width=width, collective=collective,
                  loss=f"{float(loss):.6f}", ref_loss=f"{float(want):.6f}",
                  loss_err=f"{loss_err:.2e}", grad_err=f"{grad_err:.2e}",
                  grad_scale=f"{grad_scale:.2e}", compile_s=f"{compile_s:.2f}",
                  run_s=f"{run_s:.4f}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-stage pipeline ring on 4 chips")
    args = ap.parse_args()

    # the TPU runtime's logs stay in the checkout (read when the runtime
    # starts, on the first device query)
    if "TPU_LOG_DIR" not in os.environ:
        logs = REPO / ".cache" / "tpu_logs"
        logs.mkdir(parents=True, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(logs)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); nothing was run")
    print(f"compile cache: {use_compile_cache()}", flush=True)
    if args.four_chips:
        pipeline_phase(devices)
    else:
        from repro import configs
        compiler_phase()
        validate_phase()
        bundle = configs.get("smollm-135m")
        serve_phase(bundle.model, bundle.parallel_for("decode_32k", False))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
