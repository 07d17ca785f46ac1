"""Public model API: init / forward / loss / prefill / decode, per config.

All entry points are pure functions of ``(cfg, ctx)`` closed over at jit
time; `input_specs` yields ShapeDtypeStruct stand-ins for the dry-run so no
arrays are ever materialized for the full-size configs.
"""
from __future__ import annotations

import math
import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.parallel import ParallelContext


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig, device=None):
    """Random parameters of `cfg`, made by one jitted program.

    Each leaf is drawn in float32 and cast to `cfg.dtype` inside that
    program, so no float32 copy of a weight stack is ever held: a model
    whose `cfg.dtype` parameters fill most of a chip still initializes on
    it. With `device` the parameters are made on (and committed to) that
    device; otherwise on JAX's default device.
    """
    if device is not None:
        key = jax.device_put(key, device)
    return _init_params_jit(key, cfg)


def _init_params(key, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    k_embed, k_stack, k_enc, k_out = jax.random.split(key, 4)
    params = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dt),
        "stack": T.init_stack(k_stack, cfg),
        "final_norm": L.init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (jax.random.normal(
            k_out, (cfg.vocab_size, cfg.d_model), jnp.float32)
            / math.sqrt(cfg.d_model)).astype(dt)
    if cfg.enc_dec:
        params["encoder"] = T.init_encoder(k_enc, cfg)
    if cfg.vision_tokens:
        params["vision_proj"] = (jax.random.normal(
            jax.random.fold_in(key, 7), (cfg.d_model, cfg.d_model),
            jnp.float32) * 0.02).astype(dt)
    return params


_init_params_jit = jax.jit(_init_params, static_argnames="cfg")


def params_shapes(cfg: ModelConfig):
    return jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_tokens(params, tokens, cfg: ModelConfig):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.family in ("dense", "hybrid", "vlm") and cfg.tie_embeddings:
        # gemma-family convention: scale token embeddings by sqrt(d)
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def lm_head(params, x, cfg: ModelConfig):
    w = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                        w.astype(jnp.float32))
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _embed_inputs(params, batch, cfg: ModelConfig, ctx: ParallelContext,
                  mode: str = "train"):
    """Token (+ modality-stub) embedding. Returns (x, positions, enc_out)."""
    enc_out = None
    if cfg.enc_dec and mode != "decode":
        # decode never re-encodes: cross K/V were cached at prefill
        enc_out = T.run_encoder(params["encoder"], batch["audio_frames"],
                                cfg=cfg, ctx=ctx)
    x = embed_tokens(params, batch["tokens"], cfg)
    if cfg.vision_tokens and "vision_embeds" in batch:
        v = batch["vision_embeds"] @ params["vision_proj"]
        x = jnp.concatenate([v.astype(x.dtype), x[:, v.shape[1]:]], axis=1)
    positions = batch.get("positions")
    return ctx.shard_activation(x), positions, enc_out


def forward(params, batch, *, cfg: ModelConfig, ctx: ParallelContext,
            mode: str = "train", cache=None, pos=None):
    x, positions, enc_out = _embed_inputs(params, batch, cfg, ctx, mode)
    x, new_cache, aux = T.run_stack(
        params["stack"], x, cfg=cfg, ctx=ctx, mode=mode, cache=cache,
        pos=pos, positions=positions, enc_out=enc_out)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_head(params, x, cfg)
    return logits, new_cache, aux


_CE_CHUNK = 512


def _ce_from_hidden(params, x, labels, cfg: ModelConfig):
    """Chunked softmax-CE straight from final hidden states.

    Scans over sequence chunks with a checkpointed body so the full
    (B, S, V) logits tensor is never alive — decisive for 256k vocabs.
    Returns (sum_nll, count).
    """
    b, s, _ = x.shape
    chunk = min(_CE_CHUNK, s)
    if s % chunk:
        chunk = s  # odd lengths: single chunk

    def body(carry, xs):
        xc, lc = xs                         # (B, c, D), (B, c)
        logits = lm_head(params, xc, cfg)   # (B, c, V) f32, transient
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        nll = jnp.sum((logz - gold) * mask)
        return (carry[0] + nll, carry[1] + jnp.sum(mask)), None

    nc = s // chunk
    xs = (jnp.moveaxis(x.reshape(b, nc, chunk, -1), 1, 0),
          jnp.moveaxis(labels.reshape(b, nc, chunk), 1, 0))
    (nll, cnt), _ = jax.lax.scan(jax.checkpoint(body),
                                 (jnp.zeros((), jnp.float32),
                                  jnp.zeros((), jnp.float32)), xs)
    return nll, cnt


def loss_fn(params, batch, *, cfg: ModelConfig, ctx: ParallelContext,
            aux_weight: float = 0.01):
    x, positions, enc_out = _embed_inputs(params, batch, cfg, ctx)
    x, _, aux = T.run_stack(params["stack"], x, cfg=cfg, ctx=ctx,
                            mode="train", positions=positions,
                            enc_out=enc_out)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    nll, cnt = _ce_from_hidden(params, x, batch["labels"], cfg)
    loss = nll / jnp.maximum(cnt, 1.0)
    total = loss + aux_weight * aux["moe_aux_loss"]
    return total, {"loss": loss, "moe_aux_loss": aux["moe_aux_loss"]}


# ---------------------------------------------------------------------------
# Serving entry points
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    return T.init_stack_cache(cfg, batch, max_seq, dtype=dtype)


def prefill(params, batch, cache, *, cfg: ModelConfig, ctx: ParallelContext):
    """Run the prompt through the stack, fill the cache.

    Returns (last_token_logits (B, V), cache')."""
    logits, new_cache, _ = forward(params, batch, cfg=cfg, ctx=ctx,
                                   mode="prefill", cache=cache)
    return logits[:, -1], new_cache


def decode_step(params, tokens, cache, pos, *, cfg: ModelConfig,
                ctx: ParallelContext, batch_extras=None):
    """One decode step. tokens: (B, 1); pos: scalar int32 current position.

    Returns (logits (B, V), cache')."""
    batch = {"tokens": tokens}
    if batch_extras:
        batch.update(batch_extras)
    if cfg.mrope:
        b = tokens.shape[0]
        batch.setdefault(
            "positions",
            jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(1, -1, 1),
                             (3, b, 1)))
    logits, new_cache, _ = forward(params, batch, cfg=cfg, ctx=ctx,
                                   mode="decode", cache=cache, pos=pos)
    return logits[:, -1], new_cache


# ---------------------------------------------------------------------------
# Dry-run input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: InputShape, dtype="bfloat16"):
    """ShapeDtypeStructs for every model input of (cfg, shape).

    train:   {tokens, labels [, positions/vision_embeds/audio_frames]}
    prefill: {tokens [, extras]}
    decode:  {tokens (B,1)} — the KV cache itself comes from `cache_specs`.
    """
    b, s = shape.global_batch, shape.seq_len
    kind = shape.kind
    out = {}
    if kind == "train":
        out["tokens"] = _sds((b, s), jnp.int32)
        out["labels"] = _sds((b, s), jnp.int32)
    else:  # prefill feeds the whole prompt; decode one token at a time
        out["tokens"] = _sds((b, s) if kind == "prefill" else (b, 1),
                             jnp.int32)

    seq_here = 1 if kind == "decode" else s
    if cfg.mrope:
        out["positions"] = _sds((3, b, seq_here), jnp.int32)
    if cfg.vision_tokens and kind != "decode":
        out["vision_embeds"] = _sds((b, cfg.vision_tokens, cfg.d_model),
                                    dtype)
    if cfg.enc_dec:
        enc_len = s if kind == "train" else cfg.encoder_seq_len
        if kind != "decode":
            enc_len = min(s, 32768) if kind == "prefill" else enc_len
            out["audio_frames"] = _sds((b, enc_len, cfg.d_model), dtype)
    return out


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int, dtype="bfloat16"):
    return jax.eval_shape(
        lambda: init_cache(cfg, batch, max_seq, dtype=jnp.dtype(dtype)))


# ---------------------------------------------------------------------------
# Convenience: tiny random batch for smoke tests
# ---------------------------------------------------------------------------


def dummy_batch(key, cfg: ModelConfig, batch: int, seq: int,
                kind: str = "train"):
    ks = jax.random.split(key, 4)
    tokens = jax.random.randint(ks[0], (batch, seq if kind != "decode" else 1),
                                0, cfg.vocab_size)
    out = {"tokens": tokens}
    if kind == "train":
        out["labels"] = jax.random.randint(ks[1], (batch, seq), 0,
                                           cfg.vocab_size)
    seq_here = 1 if kind == "decode" else seq
    if cfg.mrope:
        out["positions"] = jnp.broadcast_to(
            jnp.arange(seq_here, dtype=jnp.int32), (3, batch, seq_here))
    if cfg.vision_tokens and kind != "decode":
        out["vision_embeds"] = jax.random.normal(
            ks[2], (batch, min(cfg.vision_tokens, seq // 2), cfg.d_model),
            jnp.bfloat16)
    if cfg.enc_dec and kind != "decode":
        out["audio_frames"] = jax.random.normal(
            ks[3], (batch, min(cfg.encoder_seq_len, seq), cfg.d_model),
            jnp.bfloat16)
    return out
