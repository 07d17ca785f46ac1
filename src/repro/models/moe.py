"""Mixture-of-Experts layer: shared + routed experts, top-k gating.

Covers Mixtral (8 experts, top-2, softmax-renormalized gates) and
DeepSeekMoE (fine-grained: 2 shared + 64 routed, top-6).

The routed computation uses dense one-hot dispatch/combine einsums — every
token multiplies against every expert's weights with a (top-k-normalized)
combine weight that is zero for unrouted experts. On TPU this is the
deterministic, all-to-all-free baseline (compute cost = E/k × active FLOPs);
`expert_parallel=True` in the layout hillclimb shards the expert dim instead
(see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, init_mlp, mlp
from repro.models.parallel import ParallelContext


def init_moe(key, cfg: ModelConfig):
    d = cfg.d_model
    h = cfg.moe_d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    k_router, k_up, k_gate, k_down, k_shared = jax.random.split(key, 5)
    e = cfg.n_experts
    params = {
        "router": dense_init(k_router, (d, e), scale=0.02, dtype=jnp.float32),
        "we_gate": dense_init(k_gate, (e, d, h), dtype=dt),
        "we_up": dense_init(k_up, (e, d, h), dtype=dt),
        "we_down": dense_init(k_down, (e, h, d), dtype=dt),
    }
    if cfg.n_shared_experts:
        params["shared"] = init_mlp(k_shared, d, cfg.n_shared_experts * h, dt)
    return params


def router_probs(p, x, cfg: ModelConfig):
    """(tokens, E) routing probabilities and top-k indices."""
    logits = x.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.top_k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)  # renormalize gates
    return probs, topv, topi


def moe_layer(p, x, *, cfg: ModelConfig, ctx: ParallelContext):
    """x: (B, S, D) -> (out, aux) with load-balance auxiliary loss terms."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    probs, topv, topi = router_probs(p, xt, cfg)

    # combine weights: (tokens, E), zero outside top-k
    comb = jnp.zeros_like(probs)
    comb = jax.vmap(lambda c, i, v: c.at[i].set(v))(comb, topi, topv)
    comb = comb.astype(x.dtype)

    act = jax.nn.gelu if cfg.activation == "geglu" else jax.nn.silu
    gate = jnp.einsum("td,edf->tef", xt, p["we_gate"])
    up = jnp.einsum("td,edf->tef", xt, p["we_up"])
    hidden = act(gate) * up
    expert_out = jnp.einsum("tef,efd->ted", hidden, p["we_down"])
    out = jnp.einsum("ted,te->td", expert_out, comb)

    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], xt, cfg.activation, ctx)

    # Switch-style load-balance loss: E * sum_e f_e * P_e
    e = cfg.n_experts
    dispatch = jnp.sum(jax.nn.one_hot(topi, e, dtype=jnp.float32), axis=1)
    frac_tokens = jnp.mean(dispatch, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux_loss = e * jnp.sum(frac_tokens * frac_probs)

    return out.reshape(b, s, d), {"moe_aux_loss": aux_loss}


def moe_layer_capacity(p, x, *, cfg: ModelConfig, ctx: ParallelContext,
                       capacity_factor: float = 1.25):
    """Capacity-based sorted dispatch (§Perf hillclimb, beyond-paper).

    Tokens are sorted by expert id and packed into an (E, C, D) buffer with
    C = ceil(top_k·T/E · capacity_factor); each expert multiplies only its
    buffer. FLOPs drop from E× to top_k·capacity_factor× the per-expert
    cost (≈8.5× less for DeepSeekMoE-64e-top6); overflow tokens beyond an
    expert's capacity are dropped from that expert (standard Switch/GShard
    semantics — their other top-k routes still serve them).
    """
    b, s, d = x.shape
    e = cfg.n_experts
    k = cfg.top_k
    # per-SEQUENCE dispatch: the sort/pack stays inside the data shard (a
    # global token sort would cross devices — measured 4.3× collective blowup)
    cap = int(np.ceil(k * s / e * capacity_factor))
    probs, topv, topi = router_probs(p, x.reshape(b * s, d), cfg)
    topv = topv.reshape(b, s, k)
    topi = topi.reshape(b, s, k)

    def dispatch_row(xr, ir, wr):
        """xr: (S, D); ir/wr: (S, k) -> buffer (E, C, D) + combine info."""
        flat_e = ir.reshape(s * k)
        flat_tok = jnp.repeat(jnp.arange(s), k)
        flat_w = wr.reshape(s * k)
        order = jnp.argsort(flat_e)
        se, st, sw = flat_e[order], flat_tok[order], flat_w[order]
        start = jnp.searchsorted(se, jnp.arange(e), side="left")
        rank = jnp.arange(s * k) - start[se]
        keep = rank < cap
        slot = jnp.where(keep, se * cap + rank, e * cap)  # overflow row
        buf = jnp.zeros((e * cap + 1, d), xr.dtype).at[slot].set(xr[st])
        return buf[:-1].reshape(e, cap, d), (st, sw, keep, slot)

    buf, (st, sw, keep, slot) = jax.vmap(dispatch_row)(x, topi, topv)

    act = jax.nn.gelu if cfg.activation == "geglu" else jax.nn.silu
    gate = jnp.einsum("becd,edf->becf", buf, p["we_gate"])
    up = jnp.einsum("becd,edf->becf", buf, p["we_up"])
    hidden = act(gate) * up
    out_buf = jnp.einsum("becf,efd->becd", hidden, p["we_down"])

    def combine_row(ob, st, sw, keep, slot):
        flat = ob.reshape(e * cap, d)
        contrib = flat[jnp.where(keep, slot, 0)] \
            * (sw * keep)[:, None].astype(flat.dtype)
        return jnp.zeros((s, d), flat.dtype).at[st].add(contrib)

    out = jax.vmap(combine_row)(out_buf, st, sw, keep, slot)

    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], x, cfg.activation, ctx)

    dispatch = jnp.sum(jax.nn.one_hot(topi, e, dtype=jnp.float32), axis=2)
    aux_loss = e * jnp.sum(jnp.mean(dispatch.reshape(b * s, e), 0)
                           * jnp.mean(probs, 0))
    return out, {"moe_aux_loss": aux_loss}


def moe_layer_ep_a2a(p, x, *, cfg: ModelConfig, ctx: ParallelContext,
                     capacity_factor: float = 1.25):
    """Expert-parallel MoE with explicit all-to-all (shard_map).

    The textbook TPU MoE flow (§Perf hillclimb):
      1. route + pack locally (per shard) into an (E, C_loc, D) buffer,
      2. all-to-all over the `model` axis: each device keeps its E/m experts
         and receives every shard's rows for them → (E/m, m·C_loc, D),
      3. local FFN with expert-sharded weights (no psum at all),
      4. inverse all-to-all + local weighted combine.
    Collective cost per layer = 2 all-to-alls of ~top_k·cf·tokens·D bytes —
    instead of the gather/AR storms GSPMD emits for the jnp scatter forms.
    """
    from jax.sharding import PartitionSpec as P
    m = ctx.axis_size(ctx.model_axis)
    e = cfg.n_experts
    if ctx.mesh is None or m == 1 or e % m:
        # ep_a2a needs n_experts % model_axis == 0. The capacity-gather
        # fallback measured WORSE than dense under GSPMD (mixtral train:
        # collective 1.0 → 10.8 s — EXPERIMENTS.md §Perf), so fall back to
        # the dense-dispatch baseline instead.
        if ctx.mesh is not None and m > 1:
            return moe_layer(p, x, cfg=cfg, ctx=ctx)
        return moe_layer_capacity(p, x, cfg=cfg, ctx=ctx,
                                  capacity_factor=capacity_factor)
    b, s, d = x.shape
    k = cfg.top_k
    # local token count: batch over data axes, seq over model (seq-parallel)
    bdiv = ctx.batch_size_divisor if b % ctx.batch_size_divisor == 0 else 1
    s_loc = s // m if s % m == 0 else s
    t_loc = (b // bdiv) * s_loc
    cap = int(np.ceil(k * t_loc / e * capacity_factor))
    act = jax.nn.gelu if cfg.activation == "geglu" else jax.nn.silu

    def body(xl, router, wg, wu, wd):
        bl, sl, _ = xl.shape
        tl = bl * sl
        xt = xl.reshape(tl, d)
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, k)
        topv = (topv / jnp.sum(topv, -1, keepdims=True)).astype(xl.dtype)

        flat_e = topi.reshape(tl * k)
        flat_tok = jnp.repeat(jnp.arange(tl), k)
        flat_w = topv.reshape(tl * k)
        order = jnp.argsort(flat_e)
        se, st, sw = flat_e[order], flat_tok[order], flat_w[order]
        start = jnp.searchsorted(se, jnp.arange(e), side="left")
        rank = jnp.arange(tl * k) - start[se]
        keep = rank < cap
        slot = jnp.where(keep, se * cap + rank, e * cap)
        buf = jnp.zeros((e * cap + 1, d), xl.dtype).at[slot].set(xt[st])
        buf = buf[:-1].reshape(e, cap, d)

        # exchange: (E, C, D) -> (E/m, m·C, D) rows for MY experts
        buf = jax.lax.all_to_all(buf, ctx.model_axis, split_axis=0,
                                 concat_axis=1, tiled=True)
        gate = jnp.einsum("ecd,edf->ecf", buf, wg)
        up = jnp.einsum("ecd,edf->ecf", buf, wu)
        out_buf = jnp.einsum("ecf,efd->ecd", act(gate) * up, wd)
        # inverse exchange: rows return to their source shard
        out_buf = jax.lax.all_to_all(out_buf, ctx.model_axis, split_axis=1,
                                     concat_axis=0, tiled=True)

        flat = out_buf.reshape(e * cap, d)
        contrib = flat[jnp.where(keep, slot, 0)] \
            * (sw * keep).astype(flat.dtype)[:, None]
        out = jnp.zeros((tl, d), flat.dtype).at[st].add(contrib)

        disp = jnp.sum(jax.nn.one_hot(topi, e, dtype=jnp.float32), axis=1)
        aux = e * jnp.sum(jnp.mean(disp, 0) * jnp.mean(probs, 0))
        aux = jax.lax.pmean(aux, ctx.mesh.axis_names)
        return out.reshape(bl, sl, d), aux

    bspec = ctx.batch_spec if b % ctx.batch_size_divisor == 0 else None
    sspec = ctx.model_axis if s % m == 0 else None
    x_spec = P(bspec, sspec, None)
    out, aux = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(x_spec, P(), P(ctx.model_axis, None, None),
                  P(ctx.model_axis, None, None),
                  P(ctx.model_axis, None, None)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], p["we_gate"], p["we_up"], p["we_down"])

    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], x, cfg.activation, ctx)
    return out, {"moe_aux_loss": aux}


def moe_layer_expert_parallel(p, x, *, cfg: ModelConfig, ctx: ParallelContext):
    """Expert-parallel variant: experts sharded over the `model` axis.

    The dispatch one-hot contraction becomes an all-to-all-like pattern under
    GSPMD (tokens × expert-sharded weights). Used by the §Perf hillclimb; the
    math is identical to ``moe_layer``.
    """
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    probs, topv, topi = router_probs(p, xt, cfg)
    comb = jnp.zeros_like(probs)
    comb = jax.vmap(lambda c, i, v: c.at[i].set(v))(comb, topi, topv)
    comb = comb.astype(x.dtype)

    act = jax.nn.gelu if cfg.activation == "geglu" else jax.nn.silu
    ma = ctx.model_axis
    e = cfg.n_experts

    def ep(w):  # shard expert dim when divisible
        if ctx.mesh is None or e % ctx.axis_size(ma):
            return w
        return ctx.constrain(w, ma, *([None] * (w.ndim - 1)))

    gate = jnp.einsum("td,edf->tef", xt, ep(p["we_gate"]))
    up = jnp.einsum("td,edf->tef", xt, ep(p["we_up"]))
    hidden = act(gate) * up
    if ctx.mesh is not None and e % ctx.axis_size(ma) == 0:
        hidden = ctx.constrain(hidden, None, ma, None)
    expert_out = jnp.einsum("tef,efd->ted", hidden, ep(p["we_down"]))
    out = jnp.einsum("ted,te->td", expert_out, comb)
    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], xt, cfg.activation, ctx)

    dispatch = jnp.sum(jax.nn.one_hot(topi, e, dtype=jnp.float32), axis=1)
    aux_loss = e * jnp.sum(jnp.mean(dispatch, 0) * jnp.mean(probs, 0))
    return out.reshape(b, s, d), {"moe_aux_loss": aux_loss}
