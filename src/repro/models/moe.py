"""Mixture-of-Experts with capacity-based grouped dispatch.

Expert-parallel layout: experts shard over the model axis, tokens over the
batch axes.  Dispatch is *grouped*: tokens are viewed as (groups, T_g) with
group boundaries aligned to the batch sharding, so position-in-expert
counters (cumsum) and the dispatch gathers stay local to each data shard;
each expert buffer has a per-group capacity slice.  The combine gather over
the expert-sharded buffers is the layer's all-to-all-equivalent — the paper's
planner classifies exactly this channel as *out-of-order* (data-dependent
routing is not affine), requiring the addressable-buffer lowering, unlike the
FIFO channels of the dense stream (DESIGN.md §Arch-applicability).

Load-balancing auxiliary loss (Switch-style) is returned alongside.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from .common import PSpec
from .sharding import Rules


def moe_plan(cfg: ModelConfig) -> Dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    return {
        "router": PSpec((D, E), ("wfsdp", None), "normal", 1.0),
        "wi_gate": PSpec((E, D, F), ("experts", "wfsdp", None), "normal", 1.0),
        "wi_up": PSpec((E, D, F), ("experts", "wfsdp", None), "normal", 1.0),
        "wo": PSpec((E, F, D), ("experts", None, "wfsdp"), "normal", 1.0),
    }


def _num_groups(rules: Rules, batch: int) -> int:
    axes = rules._axes_for("batch", batch, set())
    return int(np.prod([rules.mesh.shape[a] for a in axes])) or 1


def apply_moe(p, x, cfg: ModelConfig, rules: Rules) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) → (y, aux_loss)."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    G = _num_groups(rules, B)
    T = B * S
    Tg = T // G
    cap = int(np.ceil(Tg * K / E * cfg.capacity_factor))
    cap = max(4, ((cap + 3) // 4) * 4)

    xf = x.reshape(G, Tg, D)
    xf = rules.constrain(xf, "batch", None, "embed_act")

    logits = jnp.einsum("gtd,de->gte", xf, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, K)                  # (G,Tg,K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # Switch aux loss: mean fraction routed vs mean router prob per expert.
    frac = jnp.mean(jax.nn.one_hot(eidx[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    aux = E * jnp.sum(frac * probs.mean((0, 1)))

    # position-in-expert counters, local per group (token-major, choice-minor).
    # Sort-based: O(T·K log) int32 work instead of a (T·K, E) one-hot cumsum
    # (which materializes 134 GB on the qwen3 train cell).
    TgK = Tg * K
    eidf = eidx.reshape(G, TgK)
    order = jnp.argsort(eidf, axis=1, stable=True)              # (G,TgK)
    sorted_e = jnp.take_along_axis(eidf, order, axis=1)
    ar = jnp.broadcast_to(jnp.arange(TgK)[None], (G, TgK))
    new_run = jnp.concatenate(
        [jnp.ones((G, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]], axis=1)
    run_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(new_run, ar, 0), axis=1)
    pos_sorted = ar - run_start                                  # rank in expert
    pos = jnp.zeros((G, TgK), jnp.int32).at[
        jnp.arange(G)[:, None], order].set(pos_sorted)
    pos = pos.reshape(G, Tg, K)
    keep = pos < cap
    pos = jnp.where(keep, pos, cap - 1)

    # dispatch: buffer slot (g, e, c) ← token index within group
    tok_ids = jnp.broadcast_to(jnp.arange(Tg)[None, :, None], (G, Tg, K))
    disp = jnp.zeros((G, E, cap), jnp.int32)
    disp = disp.at[
        jnp.arange(G)[:, None, None], eidx, pos
    ].set(jnp.where(keep, tok_ids, 0), mode="drop")
    slot_used = jnp.zeros((G, E, cap), jnp.bool_).at[
        jnp.arange(G)[:, None, None], eidx, pos
    ].set(keep, mode="drop")

    xe = jnp.take_along_axis(                             # (G,E,cap,D)
        xf[:, None], disp[..., None].astype(jnp.int32), axis=2)
    xe = jnp.where(slot_used[..., None], xe, 0)
    xe = rules.constrain(xe, "batch", "experts", None, "embed_act")

    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, p["wi_gate"])) \
        * jnp.einsum("gecd,edf->gecf", xe, p["wi_up"])
    ye = jnp.einsum("gecf,efd->gecd", h, p["wo"])
    ye = rules.constrain(ye, "batch", "experts", None, "embed_act")

    # combine: weight each slot by its gate, scatter-add back to tokens
    # *locally on each expert shard*, then ONE bf16 psum over the expert
    # (model) axis — expressed with shard_map because GSPMD lowers both the
    # naive gather (K× the activation bytes through the all-reduce) and a
    # jnp scatter (worse) poorly.  This is the planner's verdict implemented
    # by hand: the combine channel is out-of-order (data-dependent routing),
    # so it pays one addressable-buffer reduction — but only 1× the token
    # activations, in bf16.
    gate_buf = jnp.zeros((G, E, cap), jnp.float32).at[
        jnp.arange(G)[:, None, None], eidx, pos
    ].set(jnp.where(keep, gate, 0.0), mode="drop")

    batch_part = rules._axes_for("batch", B, set())
    expert_part = rules._axes_for("experts", E, set(batch_part))
    from jax.sharding import PartitionSpec as P

    def pp(*parts):
        def one(axes):
            if not axes:
                return None
            return axes[0] if len(axes) == 1 else tuple(axes)
        return P(*[one(a) for a in parts])

    def combine_local(ye_l, disp_l, gate_l):
        G_l = ye_l.shape[0]
        contrib = (ye_l.astype(jnp.float32) * gate_l[..., None]).astype(x.dtype)
        y_l = jnp.zeros((G_l, Tg, D), x.dtype).at[
            jnp.arange(G_l)[:, None, None], disp_l
        ].add(contrib, mode="drop")
        for ax in expert_part:
            y_l = jax.lax.psum(y_l, ax)
        return y_l

    y = jax.shard_map(
        combine_local, mesh=rules.mesh,
        in_specs=(pp(batch_part, expert_part, (), ()),
                  pp(batch_part, expert_part, ()),
                  pp(batch_part, expert_part, ())),
        out_specs=pp(batch_part, (), ()), check_vma=False,
    )(ye, disp, gate_buf)
    y = rules.constrain(y.reshape(B, S, D), "batch", "seq", "embed_act")
    return y.astype(x.dtype), aux.astype(jnp.float32)
