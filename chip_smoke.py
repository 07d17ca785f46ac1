#!/usr/bin/env python3
"""Bring-up check of the PerLLM serving path on TPU chips.

    python chip_smoke.py            # one chip: kernels, engine, parity, fleet
    python chip_smoke.py --chips 4  # four chips: the four-replica fleet only

One process owns the chip(s) and runs every phase. It drives the path a
user calls: `init_params` -> `ServingEngine` (paged KV cache) ->
prefill/decode, and `PerLLMServer` over a fleet built by
`repro.launch.serve.build_fleet`. Weights are random, made from `--seed`.

Phases (one chip), each printing one line of its numbers:
  kernels  the three Pallas attention kernels at Yi-6B's head layout in
           bf16, lowered to Mosaic, against their jnp oracles
  engine   Yi-6B at its published config, full depth, answering 8
           requests of mixed prompt lengths
  parity   Yi-6B widths at 4 layers: the engine's logits after prefill and
           after the first decode step against a float32 forward
  fleet    PerLLMServer (perllm policy) over 2 Yi-6B edge replicas and a
           Yi-9B cloud replica, depth-cut so all three fit one chip

With `--chips 4` only the fleet runs, as 3 edge replicas and 1 cloud
replica, each engine on its own chip, and again with every engine on
chip 0; the two runs must choose the same server for every request and
generate the same greedy tokens.

The last line of standard output is `{"ok": true, "device": {...}}`. Any
failure raises, so the exit code is non-zero and that line is not
printed. Without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    build_fleet, serve_fleet, synthetic_prompts,
)
from repro.models import cpu_context, forward, init_params  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402

# bf16 kernel outputs against their float32-softmax oracles: the tolerance
# tests/test_kernels.py holds the kernels to
KERNEL_TOL = 2e-2
# bf16 engine logits against a float32 forward of the same weights
# (matmuls at "highest" precision): the error's L2 norm relative to the
# reference's, and its largest element. Yi-6B widths at 4 layers on the
# CPU backend give about 1.2e-2 and 5.4e-2 over a 64000-token vocabulary.
PARITY_REL_L2 = 3e-2
PARITY_MAX_ABS = 0.15

# the one-chip engine: 8 lanes, prompts in three pow-2 prefill buckets
ENGINE_PROMPT_LENS = (17, 23, 30, 41, 50, 64, 97, 128)
# fleet depth cuts: an eighth of Yi-6B's 32 and Yi-9B's 48 layers, so
# every replica of a fleet fits one chip together
EDGE_LAYERS = 4
CLOUD_LAYERS = 6
# fleet requests: 8 new tokens within 1 s of modelled time. One edge
# replica cannot meet that for all 12 at once, so the perllm policy
# spreads them over every replica (at the CLI's 4 s it keeps all on edge 0)
FLEET_MAX_NEW = 8
FLEET_DEADLINE_S = 1.0


def _yi_heads():
    cfg = get_config("yi-6b")
    return cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim


def _close(name, out, ref, tol):
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol,
                               err_msg=f"{name} vs its oracle")
    return float(np.max(np.abs(out - ref)))


def phase_kernels(*, prefill_lens=(8, 512), cache_len=2048, batch=8,
                  page=16, seed=0):
    """flash / decode / paged attention at Yi-6B's head layout in bf16
    against the oracles in `repro.kernels.ref`."""
    hq, hkv, d = _yi_heads()
    scale = d ** -0.5
    keys = iter(jax.random.split(jax.random.key(seed), 16))

    def rnd(shape):
        return jax.random.normal(next(keys), shape,
                                 jnp.float32).astype(jnp.bfloat16)

    def heads_first(x):
        return jnp.swapaxes(x, 1, 2)

    errs = {}
    for s in prefill_lens:
        q, k, v = rnd((1, s, hq, d)), rnd((1, s, hkv, d)), rnd((1, s, hkv, d))
        out = ops.flash_attention(q, k, v, causal=True, scale=scale)
        ref = ops.flash_attention_ref(heads_first(q), heads_first(k),
                                      heads_first(v), causal=True,
                                      scale=scale)
        errs[f"flash_s{s}"] = _close(f"flash_attention S={s}", out,
                                     heads_first(ref), KERNEL_TOL)

    q = rnd((batch, 1, hq, d))
    k, v = rnd((batch, cache_len, hkv, d)), rnd((batch, cache_len, hkv, d))
    valid = cache_len * 3 // 4 + 1
    out = ops.decode_attention(q, k, v, valid, scale=scale)
    ref = ops.decode_attention_ref(q[:, 0], heads_first(k), heads_first(v),
                                   valid, scale=scale)
    errs["decode"] = _close("decode_attention", out[:, 0], ref, KERNEL_TOL)

    n_pages = cache_len // page
    n_pool = batch * n_pages + 1
    k_pages, v_pages = rnd((n_pool, page, hkv, d)), rnd((n_pool, page, hkv, d))
    tables = jax.random.permutation(next(keys), n_pool)[:batch * n_pages]
    tables = tables.reshape(batch, n_pages).astype(jnp.int32)
    ragged = jnp.asarray([(i + 1) * cache_len // batch - 3
                          for i in range(batch)], jnp.int32)
    out = ops.paged_attention(q, k_pages, v_pages, tables, ragged,
                              scale=scale)
    ref = ops.paged_attention_ref(q[:, 0], heads_first(k_pages),
                                  heads_first(v_pages), tables, ragged,
                                  scale=scale)
    errs["paged"] = _close("paged_attention", out[:, 0], ref, KERNEL_TOL)

    print(f"kernels: heads={hq}/{hkv} head_dim={d} bf16 "
          f"interpret={ops._auto_interpret(None)} "
          + " ".join(f"{n}_max_err={e:.3e}" for n, e in errs.items())
          + f" tol={KERNEL_TOL}", flush=True)
    return errs


def phase_engine(cfg, *, max_batch=8, max_seq=512,
                 prompt_lens=ENGINE_PROMPT_LENS, max_new=16, seed=0):
    """One paged engine answering len(prompt_lens) requests, twice: a
    warm-up pass that compiles every shape, then the measured pass."""
    full = get_config(cfg.arch_id.removesuffix("-smoke")).n_layers
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_params(jax.random.key(seed), cfg, device=dev))
    init_s = time.perf_counter() - t0
    eng = ServingEngine(cfg, params, max_batch=max_batch, max_seq=max_seq,
                        paged=True, device=dev)
    rng = np.random.default_rng(seed)

    def submit_all():
        return [eng.submit(rng.integers(0, cfg.vocab_size, n).tolist(),
                           max_new_tokens=max_new) for n in prompt_lens]

    t0 = time.perf_counter()
    submit_all()
    eng.run_until_idle()
    setup_s = time.perf_counter() - t0
    compiles = (eng.prefill_compiles, eng.decode_compiles)

    reqs = submit_all()
    eng.step()                 # admits (prefills) every request + 1 decode
    if eng.queue:
        raise RuntimeError(f"{len(eng.queue)} requests not admitted in the "
                           f"first step")
    steps = 0
    t0 = time.perf_counter()
    while eng.active_slots:
        eng.step()             # pure decode: every lane is already admitted
        steps += 1
    decode_s = time.perf_counter() - t0
    bad = [(r.rid, len(r.generated)) for r in reqs
           if not r.done or len(r.generated) != max_new]
    if bad:
        raise RuntimeError(f"requests without {max_new} tokens: {bad}")
    if (eng.prefill_compiles, eng.decode_compiles) != compiles:
        raise RuntimeError("the measured pass compiled a new shape")

    mem = dev.memory_stats() or {}
    peak, limit = mem.get("peak_bytes_in_use"), mem.get("bytes_limit")
    free = limit - peak if peak is not None and limit is not None else None
    print(f"engine: {cfg.arch_id} layers={cfg.n_layers}/{full} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} {cfg.dtype} paged "
          f"max_batch={max_batch} max_seq={max_seq} "
          f"requests={len(reqs)}x{max_new}tok served={len(reqs)} "
          f"init_params_s={init_s:.2f} "
          f"setup_s={setup_s:.2f} (compile+warm-up pass; "
          f"{compiles[0]} prefill + {compiles[1]} decode shapes) "
          f"decode_steps_per_s={steps / decode_s:.2f} over {steps} steps "
          f"(smoke reading, not a benchmark) "
          f"peak_bytes_in_use={peak} bytes_limit={limit} "
          f"bytes_free_at_peak={free}", flush=True)
    return {"steps_per_s": steps / decode_s, "peak_bytes_in_use": peak}


def phase_parity(cfg, *, prompt_len=32, seed=0):
    """The engine's bf16 programs against a float32 forward.

    `prompt_len` is a power of two, so the prompt fills its prefill
    bucket exactly as the engine would pad it. The reference is the plain
    (cache-free) forward over prompt + first token, so it shares no cache
    code with the engine."""
    dev = jax.devices()[0]
    params = init_params(jax.random.key(seed), cfg, device=dev)
    eng = ServingEngine(cfg, params, max_batch=1, max_seq=2 * prompt_len,
                        paged=True, device=dev)
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).tolist()
    # the prefill the engine's admission program runs, on its own
    prefill_logits, one_cache = jax.jit(eng._prefill_cache)(
        eng.params, {"tokens": eng._put([prompt])}, eng._put(prompt_len - 1))
    first = int(jnp.argmax(prefill_logits[0]))
    decode_logits, _ = eng._decode(eng.params, eng._put([[first]]),
                                   one_cache, eng._put([prompt_len]))

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), eng.params)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: forward(
            p, {"tokens": t}, cfg=cfg32, ctx=cpu_context(),
            mode="train")[0])(params32, eng._put([prompt + [first]]))
    ref = np.asarray(ref[0], np.float32)
    out = {}
    for name, got, want in (("prefill", prefill_logits[0], ref[-2]),
                            ("decode", decode_logits[0], ref[-1])):
        err = np.asarray(got, np.float32) - want
        rel_l2 = float(np.linalg.norm(err) / np.linalg.norm(want))
        max_abs = float(np.max(np.abs(err)))
        out[name] = (rel_l2, max_abs)
        if not (rel_l2 <= PARITY_REL_L2 and max_abs <= PARITY_MAX_ABS):
            raise AssertionError(
                f"{name} logits vs float32: rel_l2={rel_l2:.3e} "
                f"max_abs={max_abs:.3e} (limits {PARITY_REL_L2}, "
                f"{PARITY_MAX_ABS})")
    print(f"parity: {cfg.arch_id} layers={cfg.n_layers} "
          f"d_model={cfg.d_model} prompt={prompt_len} "
          + " ".join(f"{n}_rel_l2={r:.3e} {n}_max_abs={m:.3e}"
                     for n, (r, m) in out.items())
          + f" max_abs_ref={float(np.max(np.abs(ref[-2:]))):.3f} "
          f"limits rel_l2<={PARITY_REL_L2} max_abs<={PARITY_MAX_ABS}",
          flush=True)
    return out


def phase_fleet(edge_cfg, cloud_cfg, *, n_edge=2, n_requests=12,
                devices=None, seed=0, label="fleet"):
    """PerLLMServer with the perllm policy over paged engines. Returns
    ((server, greedy tokens) per request, the server)."""
    srv = build_fleet(edge_cfg, cloud_cfg, n_edge=n_edge, policy="perllm",
                      max_batch=4, max_seq=64,
                      kv=dict(paged=True, kv_block_tokens=16),
                      devices=devices, seed=seed)
    vocab = min(edge_cfg.vocab_size, cloud_cfg.vocab_size)
    t0 = time.perf_counter()
    reqs = serve_fleet(srv, synthetic_prompts(n_requests, vocab, 4, 16, seed),
                       FLEET_MAX_NEW, deadline=FLEET_DEADLINE_S)
    wall_s = time.perf_counter() - t0
    stats = srv.stats
    if stats["n_served"] != n_requests or not all(
            sr.done and len(sr.engine_req.generated) == FLEET_MAX_NEW
            for sr in reqs):
        raise RuntimeError(f"fleet served {stats['n_served']} of "
                           f"{n_requests} requests")
    placed = [str(eng.device.id) for eng in srv.engines]
    print(f"{label}: policy={srv.scheduler.name} edge={edge_cfg.arch_id}"
          f"x{n_edge}@{edge_cfg.n_layers}L cloud={cloud_cfg.arch_id}"
          f"@{cloud_cfg.n_layers}L devices={','.join(placed)} "
          f"served={stats['n_served']}/{n_requests} "
          f"per_server={stats['per_server_served']} "
          f"preempted={stats['n_preempted']} wall_s={wall_s:.2f} "
          f"(compiles included)", flush=True)
    result = [(sr.server, list(sr.engine_req.generated)) for sr in reqs]
    return result, srv


def engine_devices(eng):
    """Every device holding one of the engine's params, cache or pages."""
    leaves = jax.tree.leaves((eng.params, eng.cache,
                              eng.kv._pools if eng.paged else []))
    return {d for leaf in leaves for d in leaf.devices()}


def phase_fleet4(edge_cfg, cloud_cfg, devices, *, seed=0):
    """Four replicas, each engine on its own device, against the same
    fleet with every engine on devices[0]."""
    spread, srv = phase_fleet(edge_cfg, cloud_cfg, n_edge=3,
                              devices=devices, seed=seed, label="fleet4")
    held = [engine_devices(eng) for eng in srv.engines]
    if held != [{d} for d in devices] or len(set(devices)) != 4:
        raise RuntimeError(f"engines are not one per device: {held}")
    if not all(srv.stats["per_server_served"]):
        raise RuntimeError(f"a replica served nothing, so its device went "
                           f"untested: {srv.stats['per_server_served']}")
    del srv
    gc.collect()
    same, _ = phase_fleet(edge_cfg, cloud_cfg, n_edge=3,
                          devices=[devices[0]] * 4, seed=seed,
                          label="fleet4-on-device0")
    if spread != same:
        diff = [i for i, (a, b) in enumerate(zip(spread, same, strict=True))
                if a != b]
        raise RuntimeError(f"requests {diff} differ between one device per "
                           f"replica and all replicas on device 0")
    print(f"fleet4: {len(spread)} requests, same server and same greedy "
          f"tokens with one device per replica and with all on device 0; "
          f"servers={[s for s, _ in spread]}", flush=True)
    return spread


def fleet_configs():
    """Yi-6B edge and Yi-9B cloud at published widths, depth-cut."""
    return (dataclasses.replace(get_config("yi-6b"), n_layers=EDGE_LAYERS),
            dataclasses.replace(get_config("yi-9b"), n_layers=CLOUD_LAYERS))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-replica fleet, one engine "
                         "per chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this check runs only on a TPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    if ops._auto_interpret(None):
        raise RuntimeError("Pallas kernels would run in interpret mode on "
                           "the TPU")
    cache_dir = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)} "
          f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)

    edge_cfg, cloud_cfg = fleet_configs()
    if args.chips == 4:
        phase_fleet4(edge_cfg, cloud_cfg, devices[:4], seed=args.seed)
    else:
        # each phase frees its arrays before the next; engines hold
        # reference cycles through their jitted closures, hence gc
        phase_kernels(seed=args.seed)
        phase_engine(get_config("yi-6b"), seed=args.seed)
        gc.collect()
        phase_parity(dataclasses.replace(get_config("yi-6b"), n_layers=4),
                     seed=args.seed)
        gc.collect()
        phase_fleet(edge_cfg, cloud_cfg, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
