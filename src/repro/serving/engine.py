"""Continuous-batching serving engine.

A fixed pool of `max_batch` KV-cache slots; requests are admitted into free
slots (prefill) and all active slots decode together each step with
per-slot positions (the `update_cache_seq` vector-pos path). This is the
execution layer a PerLLM "server" runs — the scheduler decides *which*
server a request goes to, the engine decides *how* it runs there.

With `paged=True` the engine's KV capacity is a `PagedKVCache` block pool
instead of the implicit `max_batch × max_seq` dense reservation: admission
allocates `ceil((prompt+max_new)/block_tokens)` blocks up front and stalls
(FIFO) when the pool is exhausted — memory, not lane count, is what bounds
the batch. Eviction snapshots the slot's KV into the request's pages
(`evict` → `Request.kv`), so a preempted request `resubmit`-ted to the
same engine reattaches its page table and resumes decoding with **zero
re-prefill**; `release` drops a request's pages when the work moves
elsewhere. The per-slot compute view stays the dense jitted cache (pages
are scattered/gathered at evict/resume only), which keeps paged and dense
decoding bit-identical; `repro.kernels.paged_attention` is the kernel
that decodes straight from such a pool on TPU.

Each engine lives on one device (`device`, default the first): its params,
dense cache and page pool are committed there, so the jitted steps run
there and a fleet of engines can spread over a host's chips.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import List, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.obs.metrics import MetricsRegistry, counter_attr, with_aliases
from repro.models.parallel import ParallelContext, cpu_context
from repro.serving.kvcache import KVSnapshot, PagedKVCache, PageTable
from repro.serving.sampling import sample_tokens


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 32
    eos_id: int = -1          # -1: never stop early
    # runtime
    slot: int = -1
    generated: List[int] = dataclasses.field(default_factory=list)
    submitted_at: float = 0.0
    first_token_at: float = -1.0
    done_at: float = -1.0
    # paged-KV runtime: the request's block-pool pages while it holds any,
    # and the resume snapshot written by `evict` (consumed by re-admission)
    pages: Optional[PageTable] = None
    kv: Optional[KVSnapshot] = None

    @property
    def done(self) -> bool:
        return self.done_at >= 0


def _batch_axis_tree(cfg: ModelConfig, max_seq: int):
    """Which axis of each cache leaf is the batch axis (found by probing)."""
    c1 = jax.eval_shape(lambda: M.init_cache(cfg, 1, max_seq))
    c2 = jax.eval_shape(lambda: M.init_cache(cfg, 2, max_seq))
    return jax.tree.map(
        lambda a, b: next(i for i, (x, y)
                          in enumerate(zip(a.shape, b.shape, strict=True))
                          if x != y), c1, c2)


class ServingEngine:
    # registry-backed compile counters — the runtime complement to the
    # R8 static rule: decode must stay at one compile per (batch, 1)
    # token shape, prefill at one per pow-2 seq bucket
    decode_compiles = counter_attr("engine.decode_compiles")
    prefill_compiles = counter_attr("engine.prefill_compiles")

    def __init__(self, cfg: ModelConfig, params, max_batch: int = 8,
                 max_seq: int = 1024, ctx: Optional[ParallelContext] = None,
                 temperature: float = 0.0, seed: int = 0,
                 paged: bool = False, kv_blocks: Optional[int] = None,
                 kv_block_tokens: int = 16, prefix_sharing: bool = True,
                 device: Optional[jax.Device] = None):
        self.cfg = cfg
        self.device = device if device is not None else jax.devices()[0]
        self.params = jax.device_put(params, self.device)
        self.ctx = ctx or cpu_context()
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.temperature = temperature
        with jax.default_device(self.device):
            self.cache = M.init_cache(cfg, max_batch, max_seq)
        self._axis = _batch_axis_tree(cfg, max_seq)
        self.positions = np.zeros(max_batch, np.int32)
        self.cur_tokens = np.zeros(max_batch, np.int32)
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.queue: List[Request] = []
        self._rid = itertools.count()
        self._key = jax.random.key(seed)
        self.completed: List[Request] = []
        self.metrics = MetricsRegistry()
        self._compiled_shapes: set = set()
        self.n_prefills = 0       # prompts actually prefilled (resumes skip)
        self.n_prefix_hits = 0        # admissions that reused a shared prefix
        self.prefix_tokens_reused = 0  # prompt tokens those hits skipped
        self.prefix_sharing = bool(prefix_sharing)
        # DVFS pacing hint: the relative clock frequency this engine's host
        # is currently running at. Compute (`step`) is frequency-blind —
        # the same tokens come out — but the runtime that clocks the engine
        # (PerLLMServer's per-engine tick cadence) stretches each decode
        # step by 1/freq_scale, mapping scheduler-chosen tiers onto real
        # decode-step pacing. Set via `set_freq_scale`.
        self.freq_scale = 1.0
        self.paged = paged
        self.kv: Optional[PagedKVCache] = None
        if paged:
            # default pool: the dense reservation's worth of blocks
            n_blocks = kv_blocks if kv_blocks is not None \
                else max_batch * (max_seq // kv_block_tokens)
            self.kv = PagedKVCache(cfg, n_blocks=n_blocks,
                                   block_tokens=kv_block_tokens,
                                   max_seq=max_seq, device=self.device)

        self._decode = jax.jit(
            lambda p, t, c, pos: M.decode_step(p, t, c, pos, cfg=cfg,
                                               ctx=self.ctx))

        # prompts are right-padded to power-of-2 buckets so prefill
        # compiles once per bucket, not once per prompt length; `last`
        # indexes the true final-token logits. Padded garbage keys occupy
        # slots >= plen but decode overwrites them sequentially before the
        # position mask can ever reach them. The one-slot cache is built
        # inside the program, so it starts on the engine's device.
        def _prefill_cache(p, batch, last):
            c = M.init_cache(cfg, 1, max_seq)
            logits, new_cache, _ = M.forward(p, batch, cfg=cfg,
                                             ctx=self.ctx, mode="prefill",
                                             cache=c)
            return logits[:, last], new_cache
        self._prefill = jax.jit(_prefill_cache)

    # ------------------------------------------------------------------
    def submit(self, prompt: List[int], max_new_tokens: int = 32,
               eos_id: int = -1) -> Request:
        if self.paged:
            need = self.kv.blocks_for(len(prompt) + max_new_tokens)
            if need > self.kv.n_blocks:
                raise ValueError(
                    f"request needs {need} KV blocks but the pool only has "
                    f"{self.kv.n_blocks}; it could never be admitted")
        req = Request(rid=next(self._rid), prompt=list(prompt),
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      submitted_at=time.time())
        self.queue.append(req)
        return req

    def resubmit(self, req: Request) -> Request:
        """Re-enqueue a previously evicted request on this engine.

        Paged engines only: the request re-enters with its pages and
        `KVSnapshot` attached, so admission reattaches the page table and
        resumes decoding instead of re-running prefill. (Dense engines have
        nothing to reattach — submit the remainder as a new request.)"""
        assert self.paged, "resubmit needs a paged engine (KV survives)"
        assert req.slot < 0 and not req.done, req
        assert req.kv is not None and req.pages is not None, \
            "resubmit is for evicted requests holding a KV snapshot"
        self.queue.append(req)
        return req

    def release(self, req: Request) -> None:
        """Drop an evicted request's pages + snapshot (it is moving to a
        different server, or its work was abandoned)."""
        if self.paged and req.pages is not None:
            self.kv.free(req.pages)
        req.pages = None
        req.kv = None

    def set_freq_scale(self, freq: float) -> None:
        """Set the host's DVFS pacing (relative frequency, nominal 1.0)."""
        if freq <= 0.0:
            raise ValueError(f"freq_scale must be positive, got {freq}")
        self.freq_scale = float(freq)

    @property
    def active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    @property
    def kv_free_blocks(self) -> Optional[int]:
        """Free KV blocks (None when the engine is dense)."""
        return self.kv.free_blocks if self.paged else None

    @property
    def _sharing(self) -> bool:
        """Prefix sharing live on this engine (paged + enabled + the
        pool's leaf layout supports a prefix index)."""
        return self.paged and self.prefix_sharing \
            and self.kv.supports_prefix

    def _note_compile(self, kind: str, shape) -> None:
        """Count first-seen operand shapes per jitted entry point. jit
        caches on shape, so a fresh (kind, shape) key is exactly one new
        XLA compile; the counters stay flat once the shape set is warm."""
        key = (kind, tuple(shape))
        if key not in self._compiled_shapes:
            self._compiled_shapes.add(key)
            self.metrics.inc(f"engine.{kind}_compiles")

    def _put(self, host_value):
        """An int32 array of `host_value` on the engine's device."""
        return jax.device_put(np.array(host_value, np.int32), self.device)

    def _insert_slot(self, slot: int, single_cache):
        def ins(pool, one, ax):
            return jax.lax.dynamic_update_slice_in_dim(pool, one, slot, ax)
        self.cache = jax.tree.map(ins, self.cache, single_cache, self._axis)

    def _extract_slot(self, slot: int):
        def ext(pool, ax):
            return jax.lax.dynamic_slice_in_dim(pool, slot, 1, ax)
        return jax.tree.map(ext, self.cache, self._axis)

    # ------------------------------------------------------------------
    def _admit(self) -> None:
        for slot in range(self.max_batch):
            if self.slot_req[slot] is not None:
                continue
            if not self.queue:
                break
            req = self.queue[0]
            if self.paged and req.kv is not None:
                self.queue.pop(0)
                self._resume(slot, req)
                continue
            if self.paged:
                req.pages = self.kv.allocate(
                    len(req.prompt) + req.max_new_tokens,
                    prompt=req.prompt if self._sharing else None)
                if req.pages is None:
                    # KV pressure: admission stalls FIFO — but a resumable
                    # continuation further back already holds its pages
                    # (it allocates nothing) and must pass the stalled
                    # head, or its held blocks could deadlock the pool
                    ri = next((i for i, q in enumerate(self.queue)
                               if q.kv is not None), None)
                    if ri is None:
                        break
                    self._resume(slot, self.queue.pop(ri))
                    continue
            self.queue.pop(0)
            plen = len(req.prompt)
            skip = 0
            if self.paged and req.pages is not None \
                    and req.pages.shared_blocks > 0:
                skip = req.pages.shared_blocks * self.kv.block_tokens
            if skip > 0:
                # prefix hit: the table's read-shared head already holds
                # the prompt's first `skip` tokens of KV — gather the
                # pages and prefill only the suffix, one token at a time
                # through the decode step (its shape-polymorphic jit
                # serves batch 1; `allocate` guarantees skip < plen)
                one_cache = self.kv.load(req.pages, [])
                logits = None
                self._note_compile("decode", (1, 1))
                for i in range(skip, plen):
                    logits, one_cache = self._decode(
                        self.params, self._put([[req.prompt[i]]]),
                        one_cache, self._put([i]))
                last_logits = logits
                self.n_prefix_hits += 1
                self.prefix_tokens_reused += skip
            else:
                bucket = 1 << max(plen - 1, 1).bit_length()  # next pow2 >= plen
                bucket = min(bucket, self.max_seq)
                padded = req.prompt + [0] * (bucket - plen)
                prompt = self._put([padded])
                batch = {"tokens": prompt}
                if self.cfg.mrope:
                    s = prompt.shape[1]
                    batch["positions"] = self._put(np.broadcast_to(
                        np.arange(s, dtype=np.int32), (3, 1, s)))
                # deliberate shape polymorphism: the pow-2 bucketing above
                # caps this at log2(max_seq) distinct prefill shapes, and
                # `engine.prefill_compiles` counts them at runtime
                self._note_compile("prefill", prompt.shape)
                last_logits, one_cache = self._prefill(  # repro-check: disable=R8
                    self.params, batch, self._put(plen - 1))
            self.n_prefills += 1
            if self._sharing:
                # publish the prompt's full blocks while they really hold
                # its KV (pages are otherwise only written at eviction) so
                # later admissions can attach them copy-on-write
                self.kv.store_prefix(req.pages, one_cache, n_tokens=plen)
                self.kv.register_prefix(req.prompt, req.pages)
            self._key, k = jax.random.split(self._key)
            tok = int(sample_tokens(k, last_logits, self.temperature)[0])
            self._insert_slot(slot, one_cache)
            req.slot = slot
            req.generated.append(tok)
            req.first_token_at = time.time()
            self.positions[slot] = len(req.prompt)
            self.cur_tokens[slot] = tok
            self.slot_req[slot] = req
            self._maybe_finish(slot)

    def _resume(self, slot: int, req: Request) -> None:
        """Reattach an evicted request: gather its pages back into the
        slot's dense compute cache and continue decoding — no prefill."""
        snap = req.kv
        req.kv = None
        self._insert_slot(slot, self.kv.load(req.pages, snap.state))
        req.slot = slot
        self.positions[slot] = snap.position
        self.cur_tokens[slot] = snap.cur_token
        self.slot_req[slot] = req
        self._maybe_finish(slot)

    def evict(self, slot: int, keep_kv: bool = True) -> Optional[Request]:
        """Preempt the request occupying `slot`, returning its lane.

        The request is detached un-finished with its partial generation
        kept on the object. A paged engine snapshots the slot's KV into
        the request's pages first (`Request.kv`), so `resubmit` here skips
        re-prefill — unless `keep_kv=False` (a memory-pressure eviction:
        the pages go straight back to the pool, no snapshot scatter). A
        dense engine drops the KV either way (stale cache rows are
        harmless — the next admission overwrites them). The freed lane's
        `positions`/`cur_tokens` are zeroed so stale decode state can't
        leak into the next occupant's diagnostics. The caller decides
        whether the remaining tokens run here or elsewhere (and must
        `release` the pages if elsewhere)."""
        req = self.slot_req[slot]
        if req is None:
            return None
        if self.paged and not keep_kv:
            self.release(req)
        elif self.paged:
            state = self.kv.store(req.pages, self._extract_slot(slot))
            req.kv = KVSnapshot(state=state,
                                position=int(self.positions[slot]),
                                cur_token=int(self.cur_tokens[slot]))
        self.slot_req[slot] = None
        self.positions[slot] = 0
        self.cur_tokens[slot] = 0
        req.slot = -1
        return req

    def _maybe_finish(self, slot: int) -> None:
        req = self.slot_req[slot]
        if req is None:
            return
        last = req.generated[-1]
        if (len(req.generated) >= req.max_new_tokens
                or (req.eos_id >= 0 and last == req.eos_id)
                or self.positions[slot] >= self.max_seq - 1):
            req.done_at = time.time()
            self.completed.append(req)
            self.slot_req[slot] = None
            self.positions[slot] = 0
            self.cur_tokens[slot] = 0
            self.release(req)      # free-on-finish: pages return to the pool

    # ------------------------------------------------------------------
    def step(self) -> int:
        """Admit + one decode step for all active slots. Returns #active."""
        self._admit()
        active = self.active_slots
        if not active:
            return 0
        tokens = self._put(self.cur_tokens[:, None])
        pos = self._put(self.positions)
        self._note_compile("decode", tokens.shape)
        logits, self.cache = self._decode(self.params, tokens, self.cache,
                                          pos)
        self._key, k = jax.random.split(self._key)
        next_tokens = np.asarray(sample_tokens(k, logits, self.temperature))
        for slot in active:
            req = self.slot_req[slot]
            req.generated.append(int(next_tokens[slot]))
            self.positions[slot] += 1
            self.cur_tokens[slot] = next_tokens[slot]
            self._maybe_finish(slot)
        return len(active)

    def stats(self) -> dict:
        """Engine-local counters under the canonical key namespace shared
        with ``PerLLMServer.stats`` / ``SimResult.stats()`` (old spellings
        like ``prefills`` / ``prefix_tokens_reused`` ride along as
        deprecated aliases for one release)."""
        out = {
            "n_prefills": self.n_prefills,
            "n_prefix_hits": self.n_prefix_hits,
            "kv_prefill_tokens_saved": self.prefix_tokens_reused,
            "n_queued": len(self.queue),
            "n_active": len(self.active_slots),
            "n_served": len(self.completed),
        }
        if self.paged:
            out["kv_free_blocks"] = self.kv.free_blocks
            out["kv_total_blocks"] = self.kv.n_blocks
        return with_aliases(out)

    def run_until_idle(self, max_steps: int = 10_000) -> List[Request]:
        """Step until queue and slots drain. Raises if `max_steps` runs out
        with work still pending — silently returning would lose requests
        (and with paged KV a stall can also mean the queue head needs
        blocks held by evicted-but-never-released snapshots)."""
        for _ in range(max_steps):
            if not self.queue and not self.active_slots:
                return self.completed
            self.step()
        if self.queue or self.active_slots:
            raise RuntimeError(
                f"run_until_idle: {len(self.queue)} queued and "
                f"{len(self.active_slots)} active requests remain after "
                f"{max_steps} steps"
                + (f" ({self.kv.free_blocks}/{self.kv.n_blocks} KV blocks "
                   f"free)" if self.paged else ""))
        return self.completed
