"""Core transformer layers: norms, RoPE/M-RoPE, gated MLPs, attention.

Everything is a pure function of (params-dict, inputs); parameter trees are
created by the matching ``init_*`` functions. Attention supports GQA/MQA,
sliding windows, rolling KV caches (keys stored pre-rotated so slot order is
irrelevant), MLA latent caches and encoder/cross attention.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.parallel import ParallelContext


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, shape, scale: Optional[float] = None, dtype=jnp.bfloat16):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int):
    return {"scale": jnp.zeros((d,), jnp.float32)}


def rmsnorm(p, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + p["scale"])).astype(dt)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (B, S, d/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# M-RoPE (Qwen2-VL): head_dim/2 frequency slots are split into temporal /
# height / width sections, each rotated by its own position stream.
MROPE_SECTIONS = (2, 1, 1)   # relative split of the d/2 freq slots (t, h, w)


def apply_mrope(x, positions3, theta: float):
    """x: (B, S, H, D); positions3: (3, B, S) int32 (t, h, w)."""
    d = x.shape[-1]
    half = d // 2
    freqs = rope_freqs(d, theta)                       # (half,)
    total = sum(MROPE_SECTIONS)
    bounds = []
    acc = 0
    for s in MROPE_SECTIONS:
        acc += int(round(half * s / total))
        bounds.append(acc)
    bounds[-1] = half
    slot = jnp.arange(half)
    sec = (slot >= bounds[0]).astype(jnp.int32) + (slot >= bounds[1]).astype(jnp.int32)
    # pos per slot: pick t/h/w stream per frequency slot
    pos = jnp.take(positions3, sec, axis=0)            # (half, B, S) -> gather on axis 0
    pos = jnp.moveaxis(pos, 0, -1).astype(jnp.float32)  # (B, S, half)
    ang = pos * freqs                                   # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def init_mlp(key, d: int, hidden: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d, hidden), dtype=dtype),
        "w_up": dense_init(k2, (d, hidden), dtype=dtype),
        "w_down": dense_init(k3, (hidden, d), dtype=dtype),
    }


def mlp(p, x, activation: str, ctx: ParallelContext):
    gate = x @ p["w_gate"]
    up = x @ p["w_up"]
    act = jax.nn.gelu(gate, approximate=True) if activation == "geglu" \
        else jax.nn.silu(gate)
    h = act * up
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Attention (GQA / MQA, full / sliding-window, self / cross)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnDims:
    n_heads: int
    n_kv_heads: int
    head_dim: int


def attn_dims(cfg: ModelConfig) -> AttnDims:
    return AttnDims(cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim)


def init_attention(key, cfg: ModelConfig, cross: bool = False):
    d = cfg.d_model
    ad = attn_dims(cfg)
    dt = _dtype(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (d, ad.n_heads * ad.head_dim), dtype=dt),
        "wk": dense_init(k2, (d, ad.n_kv_heads * ad.head_dim), dtype=dt),
        "wv": dense_init(k3, (d, ad.n_kv_heads * ad.head_dim), dtype=dt),
        "wo": dense_init(k4, (ad.n_heads * ad.head_dim, d),
                         scale=1.0 / math.sqrt(ad.n_heads * ad.head_dim), dtype=dt),
    }


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _block_mask(qi, ki, block_q, block_k, q_offset, causal, window):
    qpos = qi * block_q + jnp.arange(block_q)[:, None] + q_offset
    kpos = ki * block_k + jnp.arange(block_k)[None, :]
    msk = jnp.ones((block_q, block_k), bool)
    if causal:
        msk = msk & (kpos <= qpos)
    if window > 0:
        msk = msk & (kpos > qpos - window)
    return msk


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_jnp(q, k, v, causal: bool = True, window: int = 0,
              scale: float = 1.0, q_offset: int = 0, block_q: int = 512,
              block_k: int = 512):
    """Memory-efficient (flash-style) attention in pure jnp.

    Double lax.scan over (q blocks × k blocks) with running-softmax state —
    peak memory is O(block_q · block_k) per (batch, head) instead of O(S²).
    The custom VJP implements the FlashAttention-2 backward: probabilities
    are recomputed from the saved per-row logsumexp instead of saving scan
    carries, so training memory stays O(S·D). The Pallas kernel replaces
    this path on real TPUs. q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D).
    """
    out, _ = _flash_fwd_impl(q, k, v, causal, window, scale, q_offset,
                             block_q, block_k)
    return out


def _flash_fwd_impl(q, k, v, causal, window, scale, q_offset, block_q,
                    block_k):
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    nq, nk = sq // block_q, sk // block_k

    qr = jnp.moveaxis(q.reshape(b, nq, block_q, hkv, g, d), 1, 0)
    kr = jnp.moveaxis(k.reshape(b, nk, block_k, hkv, d), 1, 0)
    vr = jnp.moveaxis(v.reshape(b, nk, block_k, hkv, d), 1, 0)

    def q_step(_, qx):
        qi, qb = qx                     # (), (B, bq, Hkv, G, D)
        qb32 = qb.astype(jnp.float32)

        def k_step(carry, kx):
            m, l, acc = carry
            ki, kb, vb = kx
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb32,
                           kb.astype(jnp.float32)) * scale
            msk = _block_mask(qi, ki, block_q, block_k, q_offset, causal,
                              window)
            s = jnp.where(msk[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, hkv, g, block_q), -1e30, jnp.float32),
                jnp.zeros((b, hkv, g, block_q), jnp.float32),
                jnp.zeros((b, hkv, g, block_q, d), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            k_step, init, (jnp.arange(nk), kr, vr))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return None, (out, lse)         # (B, Hkv, G, bq, D), (B, Hkv, G, bq)

    _, (blocks, lses) = jax.lax.scan(q_step, None, (jnp.arange(nq), qr))
    # blocks: (nq, B, Hkv, G, bq, D) -> (B, Sq, Hq, D)
    out = jnp.moveaxis(blocks, 0, 3)            # (B, Hkv, G, nq, bq, D)
    out = out.reshape(b, hkv, g, sq, d)         # (B, Hkv, G, Sq, D)
    out = jnp.moveaxis(out, 3, 1)               # (B, Sq, Hkv, G, D)
    out = out.reshape(b, sq, hq, d).astype(q.dtype)
    lse = jnp.moveaxis(lses, 0, 3).reshape(b, hkv, g, sq)  # (B,Hkv,G,Sq)
    return out, lse


def _flash_fwd_rule(q, k, v, causal, window, scale, q_offset, block_q,
                    block_k):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, scale, q_offset,
                               block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, window, scale, q_offset, block_q, block_k,
                    res, do):
    """FlashAttention-2 backward: p is recomputed from (q, k, lse)."""
    q, k, v, out, lse = res
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    nq, nk = sq // bq, sk // bk
    f32 = jnp.float32

    do32 = do.astype(f32)
    delta = jnp.einsum("bshd,bshd->bhs", do32,
                       out.astype(f32))                     # (B, Hq, Sq)
    delta = delta.reshape(b, hkv, g, sq)

    qr = jnp.moveaxis(
        q.reshape(b, nq, bq, hkv, g, d), 1, 0).astype(f32)
    dor = jnp.moveaxis(
        do32.reshape(b, nq, bq, hkv, g, d), 1, 0)
    lser = jnp.moveaxis(
        lse.reshape(b, hkv, g, nq, bq), 3, 0)
    deltar = jnp.moveaxis(
        delta.reshape(b, hkv, g, nq, bq), 3, 0)
    kr = jnp.moveaxis(k.reshape(b, nk, bk, hkv, d), 1, 0).astype(f32)
    vr = jnp.moveaxis(v.reshape(b, nk, bk, hkv, d), 1, 0).astype(f32)

    def q_step(carry, xs):
        dk_all, dv_all = carry          # (nk, B, bk, Hkv, D) f32 each
        qi, qb, dob, lseb, deltab = xs

        def k_step(c2, kxs):
            dqb = c2
            ki, kb, vb, dkb, dvb = kxs
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb) * scale
            msk = _block_mask(qi, ki, bq, bk, q_offset, causal, window)
            s = jnp.where(msk[None, None, None], s, -1e30)
            p = jnp.exp(s - lseb[..., None])                # (B,Hkv,G,q,k)
            dv_new = dvb + jnp.einsum("bhgqk,bqhgd->bkhd", p, dob)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", dob, vb)
            ds = p * (dp - deltab[..., None]) * scale
            dq_new = dqb + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kb)
            dk_new = dkb + jnp.einsum("bhgqk,bqhgd->bkhd", ds, qb)
            return dq_new, (dk_new, dv_new)

        dq0 = jnp.zeros((b, bq, hkv, g, d), f32)
        dqb, (dk_all, dv_all) = jax.lax.scan(
            k_step, dq0, (jnp.arange(nk), kr, vr, dk_all, dv_all))
        return (dk_all, dv_all), dqb

    dk0 = jnp.zeros((nk, b, bk, hkv, d), f32)
    dv0 = jnp.zeros((nk, b, bk, hkv, d), f32)
    (dk_all, dv_all), dq_blocks = jax.lax.scan(
        q_step, (dk0, dv0), (jnp.arange(nq), qr, dor, lser, deltar))

    dq = jnp.moveaxis(dq_blocks, 0, 1).reshape(b, sq, hq, d).astype(q.dtype)
    dk = jnp.moveaxis(dk_all, 0, 1).reshape(b, sk, hkv, d).astype(k.dtype)
    dv = jnp.moveaxis(dv_all, 0, 1).reshape(b, sk, hkv, d).astype(v.dtype)
    return dq, dk, dv


flash_jnp.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_jnp_call(q, k, v, *, causal: bool = True, window: int = 0,
                   scale: float = 1.0, q_offset: int = 0,
                   block_q: int = 512, block_k: int = 512):
    """Keyword-friendly wrapper (custom_vjp wants positional args)."""
    bq = min(block_q, q.shape[1])
    bk = min(block_k, k.shape[1])
    return flash_jnp(q, k, v, causal, window, scale, q_offset, bq, bk)


# threshold above which the jnp path switches to the chunked flash form
_CHUNK_THRESHOLD = 2048


def attn_op(q, k, v, *, causal: bool, window: int, scale: float,
            q_offset=0, ctx: ParallelContext):
    """Attention dispatch: Pallas kernel / chunked-jnp / plain sdpa."""
    sq, sk = q.shape[1], k.shape[1]
    if ctx.use_pallas:
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, window=window,
                                    q_offset=q_offset, scale=scale)
    if max(sq, sk) > _CHUNK_THRESHOLD:
        return flash_jnp_call(q, k, v, causal=causal, window=window,
                              scale=scale, q_offset=q_offset)
    qi = jnp.arange(sq)[:, None] + q_offset
    kj = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask = mask & (kj <= qi)
    if window > 0:
        mask = mask & (kj > qi - window)
    return sdpa(q, k, v, mask[None, None, None], scale)


def sdpa(q, k, v, mask, scale: float):
    """Reference scaled-dot-product attention with GQA head grouping.

    q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D); mask: broadcastable to
    (B, Hkv, G, Sq, Sk) or (B, 1, 1, Sq, Sk). Plain jnp on every backend:
    the Pallas flash kernel computes its mask from (causal, window,
    q_offset) and cannot take an arbitrary one, so ``attn_op`` dispatches
    to it before a mask exists.
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def causal_mask(sq: int, sk: int, q_offset, window: int = 0):
    """(1, 1, 1, sq, sk) boolean mask; q global pos = q_offset + i."""
    qi = jnp.arange(sq)[:, None] + q_offset
    ki = jnp.arange(sk)[None, :]
    m = ki <= qi
    if window > 0:
        m = m & (ki > qi - window)
    return m[None, None, None]


def update_cache_seq(cache_arr, new, pos):
    """Write `new` (B, s, ...) into `cache_arr` (B, S, ...) at seq offset
    `pos` — scalar (aligned batch) or (B,) vector (continuous batching)."""
    if getattr(pos, "ndim", 0) == 0 or not hasattr(pos, "ndim"):
        return jax.lax.dynamic_update_slice_in_dim(cache_arr, new, pos, 1)
    return jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(c, n, p, 0)
    )(cache_arr, new, pos)


def kv_cache_cp(n_kv_heads: int, cache_len: int, ctx: ParallelContext,
                batch: int = 0) -> bool:
    """Whether the decode KV cache is context-parallel (seq over `model`).

    Used when KV heads don't divide the model axis (MQA/GQA with few heads):
    sharding hd instead makes GSPMD all-gather the whole cache per step
    (measured 2.1 GB/step on gemma3-12b decode_32k). The CP path does a
    local partial attention per shard + cross-shard logsumexp combine.

    Only for batch-shardable decode: the batch=1 long-context shape shards
    the cache sequence over `data` instead (launch/shardings.py), and
    resharding it to `model` here would all-gather the cache every step.
    """
    if ctx.mesh is None or ctx.model_axis is None:
        return False
    if batch and (batch == 1 or batch % ctx.batch_size_divisor != 0):
        return False
    m = ctx.axis_size(ctx.model_axis)
    return m > 1 and n_kv_heads % m != 0 and cache_len % m == 0


def _decode_cp(q, cache, new_k, new_v, pos, window, scale,
               cfg: ModelConfig, ctx: ParallelContext):
    """Context-parallel single-token decode (flash-decoding across chips).

    Caches are sharded (B, S/m, Hkv, hd) along `model`; each shard updates
    its slot (if owned), computes partial (m, l, acc) and the shards combine
    with a numerically-stable logsumexp reduction (pmax + psum).
    """
    from jax.sharding import PartitionSpec as P

    b = q.shape[0]
    ad_hq, hd = q.shape[2], q.shape[3]
    hkv = new_k.shape[2]
    g = ad_hq // hkv
    cache_len = cache["k"].shape[1]
    m_axis = ctx.model_axis

    def body(q, kc, vc, nk, nv, pos):
        b = q.shape[0]          # local batch inside the shard
        idx = jax.lax.axis_index(m_axis)
        s_loc = kc.shape[1]
        offset = idx * s_loc
        slot_g = pos % window if window and window <= cache_len else pos
        local = slot_g - offset
        in_range = (local >= 0) & (local < s_loc)
        lc = jnp.clip(local, 0, s_loc - 1)
        # masked one-row update: only the owning shard writes
        cur_k = jax.lax.dynamic_slice_in_dim(kc, lc, 1, 1)
        cur_v = jax.lax.dynamic_slice_in_dim(vc, lc, 1, 1)
        kc = jax.lax.dynamic_update_slice_in_dim(
            kc, jnp.where(in_range, nk, cur_k), lc, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(
            vc, jnp.where(in_range, nv, cur_v), lc, 1)

        # local partial attention
        qg = q.reshape(b, 1, hkv, g, hd).astype(jnp.float32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                       kc.astype(jnp.float32)) * scale
        slots = offset + jnp.arange(s_loc)
        valid = (slots < jnp.minimum(pos + 1, window)
                 if window and window <= cache_len else slots <= pos)
        s = jnp.where(valid[None, None, None, None, :], s, -1e30)
        m_loc = jnp.max(s, axis=-1)                        # (B,Hkv,G,1)
        p = jnp.exp(s - m_loc[..., None])
        l_loc = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bhgqk,bkhd->bhgqd", p, vc.astype(jnp.float32))

        # cross-shard logsumexp combine
        m_g = jax.lax.pmax(m_loc, m_axis)
        corr = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * corr, m_axis)
        acc_g = jax.lax.psum(acc * corr[..., None], m_axis)
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        out = out.reshape(b, 1, hkv * g, hd).astype(q.dtype)
        return out, kc, vc

    bspec = ctx.batch_spec if b % ctx.batch_size_divisor == 0 else None
    qspec = P(bspec, None, None, None)
    cspec = P(bspec, m_axis, None, None)
    out, kc, vc = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(qspec, cspec, cspec, qspec, qspec, P()),
        out_specs=(qspec, cspec, cspec),
        check_vma=False,
    )(q, cache["k"], cache["v"], new_k, new_v, pos)
    return out, {"k": kc, "v": vc}


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int, window: int = 0,
                  dtype=None):
    ad = attn_dims(cfg)
    s = min(window, max_seq) if window else max_seq
    dt = dtype or _dtype(cfg)
    shape = (batch, s, ad.n_kv_heads, ad.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def attention_layer(p, x, *, cfg: ModelConfig, ctx: ParallelContext,
                    mode: str, cache=None, pos=None, window: int = 0,
                    positions=None, enc_out=None, enc_cache=None,
                    causal: bool = True):
    """One attention op (no residual/norm).

    mode: "train" | "prefill" | "decode" | "encode".
    Returns (out, new_cache). Keys are rotated *before* caching, so rolling
    window slots need no position bookkeeping.
    """
    ad = attn_dims(cfg)
    b, s, _ = x.shape
    scale = 1.0 / math.sqrt(ad.head_dim)

    q = _split_heads(x @ p["wq"], ad.n_heads, ad.head_dim)
    if enc_out is not None or enc_cache is not None:
        # cross attention: kv from encoder output (cached at prefill)
        if enc_cache is not None:
            k, v = enc_cache["k"], enc_cache["v"]
        else:
            k = _split_heads(enc_out @ p["wk"], ad.n_kv_heads, ad.head_dim)
            v = _split_heads(enc_out @ p["wv"], ad.n_kv_heads, ad.head_dim)
        out = attn_op(q, k, v, causal=False, window=0, scale=scale, ctx=ctx)
        out = out.reshape(b, s, ad.n_heads * ad.head_dim) @ p["wo"]
        return out, {"k": k, "v": v}

    k = _split_heads(x @ p["wk"], ad.n_kv_heads, ad.head_dim)
    v = _split_heads(x @ p["wv"], ad.n_kv_heads, ad.head_dim)

    if positions is None:
        positions = (
            jnp.broadcast_to(
                jnp.asarray(pos, jnp.int32).reshape(-1, 1), (b, s))
            if mode == "decode"
            else jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0))

    if cfg.mrope and positions.ndim == 3:
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)
    elif causal:  # encoders use their own (or no) positional scheme
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if mode in ("train", "encode") or cache is None and mode == "prefill":
        out = attn_op(q, k, v, causal=causal, window=window, scale=scale,
                      ctx=ctx)
    elif mode == "prefill":
        out = attn_op(q, k, v, causal=True, window=window, scale=scale,
                      ctx=ctx)
        cache_len = cache["k"].shape[1]
        if cache_len >= s:
            newk = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, 1)
            newv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, 1)
        else:  # rolling window smaller than the prompt: keep the last slots
            assert s % cache_len == 0, "window must divide prefill length"
            newk = jax.lax.slice_in_dim(k, s - cache_len, s, axis=1)
            newv = jax.lax.slice_in_dim(v, s - cache_len, s, axis=1)
        new_cache = {"k": newk, "v": newv}
    elif mode == "decode":
        cache_len = cache["k"].shape[1]
        if (kv_cache_cp(ad.n_kv_heads, cache_len, ctx, batch=b)
                and getattr(pos, "ndim", 0) == 0):
            out, new_cache = _decode_cp(q, cache, k, v, pos, window, scale,
                                        cfg, ctx)
            out = out.reshape(b, s, ad.n_heads * ad.head_dim) @ p["wo"]
            return out, new_cache
        slot = pos % window if window and window <= cache_len else pos
        newk = update_cache_seq(cache["k"], k, slot)
        newv = update_cache_seq(cache["v"], v, slot)
        new_cache = {"k": newk, "v": newv}
        if ctx.use_pallas:
            if getattr(pos, "ndim", 0) != 0:
                raise NotImplementedError(
                    "Pallas decode with per-slot positions needs a decode "
                    "kernel that takes a per-slot valid_len; "
                    "kernels.decode_attention takes one valid_len for the "
                    "whole batch. Decode per-slot positions with "
                    "use_pallas=False.")
            from repro.kernels import ops as kops
            vl = (jnp.minimum(pos + 1, window)
                  if window and window <= cache_len else pos + 1)
            out = kops.decode_attention(q, newk, newv, vl, scale=scale)
        else:
            ki = jnp.arange(cache_len)[None, :]
            posv = jnp.asarray(pos).reshape(-1, 1)       # scalar or (B, 1)
            valid = (ki < jnp.minimum(posv + 1, window)
                     if window and window <= cache_len else ki <= posv)
            mask = valid[:, None, None, None, :]  # (B,Hkv,G,Sq,Sk) bcast
            out = sdpa(q, newk, newv, mask, scale)
    else:
        raise ValueError(mode)

    out = out.reshape(b, s, ad.n_heads * ad.head_dim) @ p["wo"]
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig):
    m = cfg.mla
    d = cfg.d_model
    dt = _dtype(cfg)
    ks = jax.random.split(key, 6)
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": dense_init(ks[0], (d, m.q_lora_rank), dtype=dt),
        "wq_b": dense_init(ks[1], (m.q_lora_rank, cfg.n_heads * qk_hd), dtype=dt),
        "wkv_a": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                            dtype=dt),
        "wkv_b": dense_init(ks[3], (m.kv_lora_rank,
                                    cfg.n_heads * (m.qk_nope_head_dim
                                                   + m.v_head_dim)), dtype=dt),
        "wo": dense_init(ks[4], (cfg.n_heads * m.v_head_dim, d), dtype=dt),
        "q_norm": init_rmsnorm(m.q_lora_rank),
        "kv_norm": init_rmsnorm(m.kv_lora_rank),
    }


def init_mla_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    m = cfg.mla
    dt = dtype or _dtype(cfg)
    return {"ckv": jnp.zeros((batch, max_seq, m.kv_lora_rank), dt),
            "krope": jnp.zeros((batch, max_seq, m.qk_rope_head_dim), dt)}


def mla_layer(p, x, *, cfg: ModelConfig, ctx: ParallelContext, mode: str,
              cache=None, pos=None, positions=None):
    """MLA with the compressed-latent KV cache (decode caches c_kv + k_rope).

    The latent cache is the paper-faithful memory saving: per token we store
    ``kv_lora_rank + qk_rope_head_dim`` floats instead of ``2·H·hd``.
    """
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    scale = 1.0 / math.sqrt(qk_hd)

    if positions is None:
        positions = (
            jnp.broadcast_to(
                jnp.asarray(pos, jnp.int32).reshape(-1, 1), (b, s))
            if mode == "decode"
            else jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0))

    q = rmsnorm(p["q_norm"], x @ p["wq_a"]) @ p["wq_b"]
    q = q.reshape(b, s, h, qk_hd)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = x @ p["wkv_a"]
    ckv, krope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    ckv = rmsnorm(p["kv_norm"], ckv)
    krope = apply_rope(krope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]

    new_cache = None
    if mode == "decode":
        ckv = update_cache_seq(cache["ckv"], ckv, pos)
        krope = update_cache_seq(cache["krope"], krope, pos)
        new_cache = {"ckv": ckv, "krope": krope}
        t = ckv.shape[1]
        posv = jnp.asarray(pos).reshape(-1, 1)
        valid = (jnp.arange(t)[None, :] <= posv)[:, None, None, :]  # b h q t
    elif mode == "prefill" and cache is not None:
        full_ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv, 0, 1)
        full_krope = jax.lax.dynamic_update_slice_in_dim(cache["krope"],
                                                         krope, 0, 1)
        new_cache = {"ckv": full_ckv, "krope": full_krope}
        t = s
        valid = (jnp.arange(t)[None, :] <= jnp.arange(s)[:, None])[None, None]
    else:
        t = s
        valid = (jnp.arange(t)[None, :] <= jnp.arange(s)[:, None])[None, None]

    if mode == "decode":
        # weight-absorbed MLA decode (DeepSeek-V2 serving trick, §Perf):
        # attend in the r-dim latent space — the cache is never expanded to
        # per-head keys/values. Per step this reads the (S, r) latent once
        # instead of materializing (S, H, dn+dv).
        wkv = p["wkv_b"].reshape(m.kv_lora_rank, h,
                                 m.qk_nope_head_dim + m.v_head_dim)
        wk_b = wkv[:, :, :m.qk_nope_head_dim]
        wv_b = wkv[:, :, m.qk_nope_head_dim:]
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                           wk_b.astype(jnp.float32))
        logits = (jnp.einsum("bqhr,bkr->bhqk", q_lat,
                             ckv.astype(jnp.float32))
                  + jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32),
                               krope.astype(jnp.float32))) * scale
        logits = jnp.where(valid, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out_lat = jnp.einsum("bhqk,bkr->bqhr", probs,
                             ckv.astype(jnp.float32))
        out = jnp.einsum("bqhr,rhd->bqhd", out_lat,
                         wv_b.astype(jnp.float32)).astype(x.dtype)
    else:
        kv_up = ckv[:, :t] @ p["wkv_b"]
        kv_up = kv_up.reshape(b, t, h, m.qk_nope_head_dim + m.v_head_dim)
        k_nope, v = jnp.split(kv_up, [m.qk_nope_head_dim], axis=-1)
        # long-sequence path: fold the shared rope key into per-head keys so
        # MLA becomes standard attention with head_dim = nope + rope, then
        # go through the chunked/flash dispatch (O(S) memory)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope[:, :t, None, :],
                                      (b, t, h, m.qk_rope_head_dim))],
            axis=-1)
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                         (0, qk_hd - m.v_head_dim)))
        out = attn_op(q_full, k_full, vp, causal=True, window=0,
                      scale=scale, ctx=ctx)[..., :m.v_head_dim]
    out = out.reshape(b, s, h * m.v_head_dim) @ p["wo"]
    return out, new_cache
