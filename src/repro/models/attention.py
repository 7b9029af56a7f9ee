"""GQA attention: chunked (flash-style online-softmax) for train/prefill, and
sequence-sharded flash-decoding for decode.

The chunked jnp implementation is also the oracle (`ref`) for the Pallas
flash-attention kernel; on TPU `repro.kernels.flash_attention.ops` swaps in
the kernel (config `use_pallas`), the XLA path below is what the CPU dry-run
compiles.

Decode reads the KV cache with its *sequence* dimension sharded over the
model axis (ParallelConfig.kv_seq_axes): softmax max/sum and the PV
contraction reduce over that sharded axis, so GSPMD lowers them to partial
reductions + small all-reduces — flash-decoding — instead of gathering the
cache (which for long_500k would be 19 GB per layer).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .common import PSpec, bias, linear
from .layers import norm_scale, rms_head, rope
from .sharding import Rules

NEG = -1e30


def attn_plan(cfg: ModelConfig) -> Dict:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    p = {
        # fan-in is the contracted width (D in, H·hd out), not a head axis:
        # drawn per head, scores grow with the width and softmax saturates
        "wq": PSpec((D, H, hd), ("wfsdp", "heads", None), fan_in=D),
        "wk": PSpec((D, KV, hd), ("wfsdp", "kv_heads", None), fan_in=D),
        "wv": PSpec((D, KV, hd), ("wfsdp", "kv_heads", None), fan_in=D),
        "wo": PSpec((H, hd, D), ("heads", None, "wfsdp"), fan_in=H * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = PSpec((H, hd), ("heads", None), "zeros")
        p["bk"] = PSpec((KV, hd), ("kv_heads", None), "zeros")
        p["bv"] = PSpec((KV, hd), ("kv_heads", None), "zeros")
    if cfg.qk_norm:
        p["q_norm"] = norm_scale(hd)
        p["k_norm"] = norm_scale(hd)
    return p


def _project_qkv(p, x, cfg: ModelConfig, rules: Rules, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q, k = rms_head(q, p["q_norm"]), rms_head(k, p["k_norm"])
    if cfg.rope_theta:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = rules.constrain(q, "batch", "seq", "heads", None)
    k = rules.constrain(k, "batch", "seq", "kv_heads", None)
    v = rules.constrain(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def chunked_attention(q, k, v, *, causal: bool, q_offset: int = 0,
                      q_chunk: int = 2048, kv_chunk: int = 2048,
                      kv_len: Optional[jnp.ndarray] = None):
    """Online-softmax attention, O(chunk²) memory.

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd) with KV | H (GQA groups).
    Reference semantics for the Pallas flash kernel (kernels/flash_attention).
    """
    B, Sq, H, hd = q.shape
    _, Skv, KVh, _ = k.shape
    G = H // KVh
    scale = hd ** -0.5
    # GQA grouping h = g·KV + kv: splitting the (model-axis-sharded) H dim as
    # (G, KV) keeps the shard boundary on G — reshaping to (KV, G) instead
    # would cut across shards and force GSPMD to replicate q/scores.
    q = q.reshape(B, Sq, G, KVh, hd) * scale

    nq = max(1, Sq // min(q_chunk, Sq))
    cq = Sq // nq
    nk = max(1, Skv // min(kv_chunk, Skv))
    ck = Skv // nk
    qs = q.reshape(B, nq, cq, G, KVh, hd)
    ks = k.reshape(B, nk, ck, KVh, hd)
    vs = v.reshape(B, nk, ck, KVh, hd)

    q_pos = q_offset + jnp.arange(Sq).reshape(nq, cq)
    k_pos = jnp.arange(Skv).reshape(nk, ck)

    def per_q_chunk(qi, qc):
        def kv_step(carry, j):
            m, l, acc = carry
            kc, vc = ks[:, j], vs[:, j]
            s = jnp.einsum("bqghd,bkhd->bqghk", qc, kc,
                           preferred_element_type=jnp.float32)
            msk = jnp.zeros((cq, ck), jnp.float32)
            if causal:
                msk = jnp.where(q_pos[qi][:, None] >= k_pos[j][None, :], 0.0, NEG)
            if kv_len is not None:
                msk = msk + jnp.where(k_pos[j][None, :] < kv_len, 0.0, NEG)
            s = s + msk[None, :, None, None, :]
            m_new = jnp.maximum(m, s.max(-1))
            corr = jnp.exp(m - m_new)
            pexp = jnp.exp(s - m_new[..., None])
            l_new = l * corr + pexp.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqghk,bkhd->bqghd", pexp.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, cq, G, KVh), NEG, jnp.float32)
        l0 = jnp.zeros((B, cq, G, KVh), jnp.float32)
        a0 = jnp.zeros((B, cq, G, KVh, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(lambda args: per_q_chunk(*args),
                      (jnp.arange(nq), jnp.moveaxis(qs, 1, 0)))
    out = jnp.moveaxis(out, 0, 1)                       # (B, nq, cq, G, KV, hd)
    return out.reshape(B, Sq, H, hd).astype(v.dtype)


def decode_attention(q, k_cache, v_cache, kv_len, rules: Rules,
                     k_scale=None, v_scale=None):
    """One-token flash decoding against a (possibly seq-sharded) KV cache.

    q: (B, 1, H, hd); caches: (B, S, KV, hd) with seq dim sharded over
    `kv_seq` axes.  Reductions over S auto-lower to partial + all-reduce.

    int8 caches come with per-(token, head) scales (B, S, KV, 1); the scale
    is applied to the *scores* / probabilities so the big cache reads stay
    int8 — halving decode's HBM traffic (the dominant roofline term).
    """
    B, _, H, hd = q.shape
    _, S, KVh, _ = k_cache.shape
    G = H // KVh
    qg = q.reshape(B, 1, G, KVh, hd)[:, 0] * (hd ** -0.5)     # (B,G,KV,hd)
    s = jnp.einsum("bghd,bshd->bghs", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    if k_scale is not None:                                    # (B,S,KV,1)
        s = s * jnp.moveaxis(k_scale[..., 0], 1, -1)[:, None]  # (B,1,KV,S)
    valid = (jnp.arange(S) < kv_len)[None, None, None, :]
    s = jnp.where(valid, s, NEG)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(-1, keepdims=True)
    p = p / l
    if v_scale is not None:
        p = p * jnp.moveaxis(v_scale[..., 0], 1, -1)[:, None]
    out = jnp.einsum("bghs,bshd->bghd", p.astype(jnp.float32),
                     v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def attention(p, x, cfg: ModelConfig, rules: Rules, mode: str,
              positions, cache: Optional[Dict] = None,
              kv_len=None, causal: bool = True, layer_idx=None):
    """Returns (y, new_cache).

    cache = {"k": (L,B,S,KV,hd), "v": …} — the FULL stacked cache, carried
    through the layer scan so XLA keeps one aliased buffer; `layer_idx`
    selects this layer's slice.  Decode writes only the current token's
    (B,1,KV,hd) slot (dynamic-update-slice at (layer, 0, pos, 0, 0)), so the
    per-step HBM write traffic is one token, not the whole cache."""
    q, k, v = _project_qkv(p, x, cfg, rules, positions)
    new_cache = cache
    int8_kv = cache is not None and "k_scale" in cache
    if mode == "train":
        o = chunked_attention(q, k, v, causal=causal)
    elif mode == "prefill":
        o = chunked_attention(q, k, v, causal=causal)
        if cache is not None:
            new_cache = dict(cache)          # preserve non-KV keys (hybrid)
            kq, ks = _quantize_kv(k, int8_kv)
            vq, vs = _quantize_kv(v, int8_kv)
            new_cache["k"] = cache_write_layer(cache["k"], layer_idx, kq, rules)
            new_cache["v"] = cache_write_layer(cache["v"], layer_idx, vq, rules)
            if int8_kv:
                new_cache["k_scale"] = cache_write_layer(
                    cache["k_scale"], layer_idx, ks, rules)
                new_cache["v_scale"] = cache_write_layer(
                    cache["v_scale"], layer_idx, vs, rules)
    elif mode == "decode":
        pos = positions[0, 0]
        kq, ks = _quantize_kv(k, int8_kv)
        vq, vs = _quantize_kv(v, int8_kv)
        new_cache = dict(cache)              # preserve non-KV keys (hybrid)
        new_cache["k"] = cache_write_token(cache["k"], layer_idx, pos, kq, rules)
        new_cache["v"] = cache_write_token(cache["v"], layer_idx, pos, vq, rules)
        ksl = vsl = None
        if int8_kv:
            new_cache["k_scale"] = cache_write_token(
                cache["k_scale"], layer_idx, pos, ks, rules)
            new_cache["v_scale"] = cache_write_token(
                cache["v_scale"], layer_idx, pos, vs, rules)
            ksl = cache_read_layer(new_cache["k_scale"], layer_idx)
            vsl = cache_read_layer(new_cache["v_scale"], layer_idx)
        k_layer = cache_read_layer(new_cache["k"], layer_idx)
        v_layer = cache_read_layer(new_cache["v"], layer_idx)
        o = decode_attention(q, k_layer, v_layer, kv_len, rules,
                             k_scale=ksl, v_scale=vsl)
    else:
        raise ValueError(mode)
    o = rules.constrain(o, "batch", "seq", "heads", None)
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return y, new_cache


def _quantize_kv(kv, int8: bool):
    """Per-(token, head) symmetric int8 quantization of fresh K/V."""
    if not int8:
        return kv, None
    a = kv.astype(jnp.float32)
    scale = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 127.0
    q = jnp.round(a / jnp.maximum(scale, 1e-12)).astype(jnp.int8)
    return q, scale


# ------------------------------------------------------- stacked-cache ops

def cache_read_layer(cache, layer_idx):
    """(L,B,S,KV,hd) → (B,S,KV,hd) for this layer."""
    sl = jax.lax.dynamic_slice_in_dim(cache, layer_idx, 1, axis=0)
    return sl[0]


def cache_write_token(cache, layer_idx, pos, kv, rules: Rules):
    """Write one token: (B,1,KV,hd) into (L,B,S,KV,hd) at (layer, :, pos)."""
    upd = kv.astype(cache.dtype)[None]                  # (1,B,1,KV,hd)
    start = (layer_idx, 0, pos, 0, 0)
    out = jax.lax.dynamic_update_slice(cache, upd, start)
    return rules.constrain(out, None, "batch", "kv_seq", "kv_heads", None)


def cache_write_layer(cache, layer_idx, kv, rules: Rules):
    """Prefill: write a whole layer's fresh KV (padded to cache length)."""
    S_c = cache.shape[2]
    pad = S_c - kv.shape[1]
    upd = jnp.pad(kv, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cache.dtype)
    out = jax.lax.dynamic_update_slice(cache, upd[None],
                                       (layer_idx, 0, 0, 0, 0))
    return rules.constrain(out, None, "batch", "kv_seq", "kv_heads", None)


def cross_attention(p, x, enc_kv, cfg: ModelConfig, rules: Rules):
    """Decoder→encoder attention; enc_kv = (k, v) precomputed from encoder."""
    positions = jnp.zeros(x.shape[:2], jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    k, v = enc_kv
    o = chunked_attention(q, k, v, causal=False)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])
