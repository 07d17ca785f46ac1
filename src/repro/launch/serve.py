"""Serving launcher: continuous-batching engine over synthetic requests.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
        --requests 16 --max-new 8

With `--policy NAME` the launcher instead serves through a PerLLM fleet
(2 edge engines of `--arch` + 1 cloud engine of `--cloud-arch`, each
reduced with `--reduced`) scheduled by the named policy from the registry
(see `repro.core.available_policies()`):

    PYTHONPATH=src python -m repro.launch.serve --policy perllm --reduced \
        --requests 12

`--paged [KV_BLOCKS]` runs the engine(s) on the paged KV cache: admission
allocates block-pool pages (and stalls on exhaustion) instead of relying
on the dense `max_batch × max_seq` reservation; evicted requests keep
their prefill (see docs/serving.md).

`build_fleet` and `serve_fleet` are the fleet path itself; `chip_smoke.py`
drives the same functions on a TPU.
"""
import argparse
import time
from typing import List, Optional, Sequence

import jax
import numpy as np

from repro.configs import ModelConfig, get_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serving import ServingEngine


def build_fleet(edge_cfg: ModelConfig, cloud_cfg: ModelConfig, *,
                n_edge: int = 2, policy: str = "perllm", max_batch: int = 2,
                max_seq: int = 64, kv: Optional[dict] = None,
                devices: Optional[Sequence] = None, seed: int = 0):
    """A `PerLLMServer` over `n_edge` edge engines and one cloud engine.

    The cloud engine gets twice the edges' batch lanes, as the testbed
    gives the cloud twice the edges' concurrency. `kv` holds the engines'
    paged-KV keyword arguments. `devices[j]` places engine j (None: JAX's
    first device); engines of one config on one device share their
    params, made once from `seed`. Raises KeyError for an unknown policy.
    """
    from repro.cluster import paper_testbed
    from repro.core import make_policy
    from repro.serving.perllm_server import PerLLMServer

    kv = kv or {}
    # specs carry the engines' block granularity so the C5 constraint's
    # blocks-needed estimate uses the same units as the engine pools
    specs = paper_testbed(n_edge=n_edge,
                          kv_block_tokens=kv.get("kv_block_tokens", 16))
    sched = make_policy(policy, len(specs))
    devices = list(devices) if devices is not None else [None] * len(specs)
    if len(devices) != len(specs):
        raise ValueError(f"{len(specs)} engines but {len(devices)} devices")
    key = jax.random.key(seed)
    params = {}
    engines = []
    for j, dev in enumerate(devices):
        cloud = j == n_edge
        cfg = cloud_cfg if cloud else edge_cfg
        if (cfg, dev) not in params:
            params[cfg, dev] = init_params(key, cfg, device=dev)
        engines.append(ServingEngine(
            cfg, params[cfg, dev], max_batch=2 * max_batch if cloud
            else max_batch, max_seq=max_seq, device=dev, **kv))
    return PerLLMServer(specs, engines, scheduler=sched)


def synthetic_prompts(n: int, vocab: int, lo: int, hi: int,
                      seed: int = 0) -> List[List[int]]:
    """`n` random prompts with lengths drawn from [lo, hi)."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def serve_fleet(srv, prompts, max_new: int, deadline: float = 4.0):
    """Submit every prompt to the fleet with `deadline` seconds of
    modelled time and drain it; returns the `ServedRequest`s in
    submission order."""
    reqs = [srv.submit(p, max_new_tokens=max_new, deadline=deadline)
            for p in prompts]
    srv.run_until_idle()
    return reqs


def _run_fleet(args, edge_cfg: ModelConfig) -> None:
    """Edge-cloud fleet scheduled by a registry policy (`--policy`)."""
    from repro.core import available_policies

    cloud_cfg = get_config(args.cloud_arch)
    if args.reduced:
        cloud_cfg = cloud_cfg.reduced()
    try:
        srv = build_fleet(edge_cfg, cloud_cfg, policy=args.policy,
                          max_batch=args.max_batch, max_seq=args.max_seq,
                          kv=_kv_kwargs(args))
    except KeyError:
        raise SystemExit(f"unknown policy {args.policy!r}; available: "
                         + ", ".join(available_policies())) from None
    vocab = min(edge_cfg.vocab_size, cloud_cfg.vocab_size)
    t0 = time.time()
    serve_fleet(srv, synthetic_prompts(args.requests, vocab, 4, 16),
                args.max_new)
    dt = time.time() - t0
    s = srv.stats
    name = srv.scheduler.name
    if not s["n_served"]:
        print(f"{name}: served 0 requests in {dt:.1f}s")
        return
    print(f"{name}: served {s['n_served']} requests in {dt:.1f}s — "
          f"deadline_met={s['admitted_success_rate']*100:.0f}% "
          f"mean_latency={s['avg_processing_time']:.2f}s "
          f"per_server={s['per_server_served']}")


def _kv_kwargs(args) -> dict:
    if args.paged is None:
        return {}
    return dict(paged=True,
                kv_blocks=args.paged if args.paged > 0 else None,
                kv_block_tokens=args.kv_block_tokens)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=list_archs())
    ap.add_argument("--cloud-arch", default="gemma3-12b",
                    choices=list_archs(),
                    help="the cloud engine's model in --policy mode")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--policy", default=None,
                    help="serve through an edge-cloud fleet scheduled by "
                         "this registered policy (perllm, fineinfer, ...)")
    ap.add_argument("--paged", type=int, nargs="?", const=0, default=None,
                    metavar="KV_BLOCKS",
                    help="paged KV cache: allocate block-pool pages at "
                         "admission (optional pool size in blocks; bare "
                         "--paged sizes the pool to the dense equivalent)")
    ap.add_argument("--kv-block-tokens", type=int, default=16,
                    help="tokens of KV per block in --paged mode")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.policy:
        _run_fleet(args, cfg)
        return

    params = init_params(jax.random.key(0), cfg)
    eng = ServingEngine(cfg, params, max_batch=args.max_batch,
                        max_seq=args.max_seq,
                        temperature=args.temperature, **_kv_kwargs(args))
    t0 = time.time()
    for prompt in synthetic_prompts(args.requests, cfg.vocab_size, 4, 24):
        eng.submit(prompt, max_new_tokens=args.max_new)
    done = eng.run_until_idle()
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in done)
    print(f"{cfg.arch_id}: served {len(done)} requests, {toks} tokens in "
          f"{dt:.1f}s ({toks/dt:.1f} tok/s on "
          f"{eng.device.platform}:{eng.device.device_kind})")


if __name__ == "__main__":
    main()
