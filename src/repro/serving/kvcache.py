"""Paged KV cache: a fixed block pool, per-request page tables, snapshots.

The dense engine pre-books one `(max_seq, ...)` cache lane per batch slot,
so a server's KV capacity is `max_batch` regardless of how long requests
actually are — and evicting a request throws its prefill away. This module
makes KV memory a real, countable resource instead:

* `BlockAllocator` — a reference-counted free list over `n_blocks`
  fixed-size blocks; every admitted request allocates
  `ceil(tokens / block_tokens)` blocks up front and the pool's
  `free_blocks` is what schedulers observe as `ClusterView.kv_free_blocks`.
  A block's refcount is the number of page tables (plus the prefix index)
  holding it; `free` only returns a block to the pool at refcount zero,
  which is what makes prefix sharing and copy-on-write forks safe.
* `PrefixIndex` — a radix tree over *full* blocks keyed by token content.
  Prefilled prompts publish their full blocks (`register`); later
  admissions whose prompt starts with the same tokens `match` those
  resident blocks and skip that much prefill. The index holds one
  allocator reference per indexed block and evicts least-recently-touched
  leaves under pool pressure, so sharing never shrinks usable capacity.
* `PageTable` — one request's physical block ids, in logical order. Padded
  to any length with block 0 it is exactly the `block_tables` row the
  `paged_attention` kernel gathers through. Its first `shared_blocks`
  blocks are copy-on-write prefix pages: read-shared with other tables,
  never written back by `store`.
* `PagedKVCache` — the pool's storage side: for every cache-tree leaf with
  a sequence axis it keeps a `(n_blocks, block_tokens, ...)` pool and can
  scatter a slot's dense per-request cache into that request's pages
  (`store`, at eviction) and gather it back into a dense slot cache
  (`load`, at resume) — which is what lets a preempted request re-enter
  *without re-running prefill*. `publish` writes a prefilled prompt's
  full blocks as a pure function of the pools, so the engine runs it
  inside its admission program with the pools donated. Leaves with no
  sequence axis (SSM/conv states, rolling windows smaller than `max_seq`)
  are snapshotted wholesale in the returned state list; they are
  per-request O(1)-sized state, not paged memory.

Layout note: pool leaves keep each cache leaf's own layout with the
sequence axis split as `(block, block_tokens)` and moved to the front, so
`store`/`load` are pure reshapes plus one indexed scatter/gather — the
attention kernels never read these pools directly (the engine's compute
view stays the dense jitted cache); `repro.kernels.paged_attention` is the
kernel that *does* read a `(n_pool, Hkv, page, D)` pool through a page
table, for the TPU deployment where the pool is the only cache.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as M


class BlockAllocator:
    """Reference-counted free-list allocator over a fixed pool of KV
    blocks. `allocate` hands out blocks at refcount 1; `ref` adds a
    holder (a sharing page table or the prefix index); `free` drops one
    holder and only returns the block to the pool when nobody holds it."""

    def __init__(self, n_blocks: int):
        if n_blocks <= 0:
            raise ValueError(f"need a positive block pool, got {n_blocks}")
        self.n_blocks = n_blocks
        self._free: List[int] = list(range(n_blocks - 1, -1, -1))
        self._ref = [0] * n_blocks

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.n_blocks - len(self._free)

    def refcount(self, block: int) -> int:
        return self._ref[block]

    def allocate(self, n: int) -> Optional[List[int]]:
        """`n` block ids, or None if the pool can't satisfy the request
        (callers treat that as admission back-pressure, not an error)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        for i in ids:
            self._ref[i] = 1
        return ids

    def ref(self, ids: List[int]) -> None:
        """Add a holder to already-live blocks (prefix sharing / COW)."""
        for i in ids:
            if self._ref[i] <= 0:
                raise ValueError(f"ref of free KV block {i}")
            self._ref[i] += 1

    def free(self, ids: List[int]) -> None:
        for i in ids:
            if self._ref[i] <= 0:
                raise ValueError(f"double free of KV block {i}")
            self._ref[i] -= 1
            if self._ref[i] == 0:
                self._free.append(i)


class _PrefixNode:
    """One full block of a registered prompt: `key` is the block's token
    content, `block` the physical block id the index holds a ref on."""

    __slots__ = ("key", "block", "children", "parent", "stamp")

    def __init__(self, key, block, parent):
        self.key = key
        self.block = block
        self.parent = parent
        self.children = {}
        self.stamp = 0


class PrefixIndex:
    """Radix tree over full KV blocks, keyed by token content.

    Each node owns one allocator reference on its block, so indexed
    prefixes survive the registering request's release — that is what
    turns a finished request's prefill into reusable capacity. `match`
    walks the longest indexed chain of full blocks that is a strict
    prefix of `tokens` (at least one suffix token always remains, so a
    hit still produces next-token logits). Under pool pressure `reclaim`
    evicts least-recently-touched leaves; evicting a leaf whose block is
    still held by a live table merely drops the index's share."""

    def __init__(self, allocator: BlockAllocator, block_tokens: int):
        self.allocator = allocator
        self.block_tokens = block_tokens
        self._root = _PrefixNode(key=None, block=-1, parent=None)
        self._clock = 0
        self.n_nodes = 0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _nodes(self):
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            yield node

    @property
    def reclaimable_blocks(self) -> int:
        """Blocks the index could return to the pool right now: indexed
        blocks no live table shares (refcount 1 — the index's own)."""
        return sum(1 for n in self._nodes()
                   if self.allocator.refcount(n.block) == 1)

    def match(self, tokens: List[int]) -> List[int]:
        """Block ids of the longest indexed full-block strict prefix of
        `tokens`, freshening their LRU stamps."""
        bt = self.block_tokens
        limit = max(0, (len(tokens) - 1) // bt)
        blocks: List[int] = []
        node = self._root
        stamp = self._tick()
        for k in range(limit):
            child = node.children.get(tuple(tokens[k * bt:(k + 1) * bt]))
            if child is None:
                break
            child.stamp = stamp
            blocks.append(child.block)
            node = child
        return blocks

    def register(self, tokens: List[int], blocks: List[int]) -> None:
        """Index the full blocks of a just-prefilled prompt. Existing
        nodes win (content-addressed: same tokens, interchangeable
        blocks); each newly inserted node takes one allocator ref."""
        bt = self.block_tokens
        node = self._root
        stamp = self._tick()
        for k in range(min(len(tokens) // bt, len(blocks))):
            key = tuple(tokens[k * bt:(k + 1) * bt])
            child = node.children.get(key)
            if child is None:
                child = _PrefixNode(key, blocks[k], node)
                self.allocator.ref([blocks[k]])
                node.children[key] = child
                self.n_nodes += 1
            child.stamp = stamp
            node = child

    def _evict(self, node: _PrefixNode) -> None:
        del node.parent.children[node.key]
        self.n_nodes -= 1
        self.allocator.free([node.block])

    def reclaim(self, n_free_target: int) -> bool:
        """Evict LRU leaves until the allocator has `n_free_target` free
        blocks (or no useful eviction remains). Returns success."""
        while self.allocator.free_blocks < n_free_target:
            leaves = [n for n in self._nodes() if not n.children]
            if not leaves:
                return False
            owned = [n for n in leaves
                     if self.allocator.refcount(n.block) == 1]
            if not owned and self.reclaimable_blocks == 0:
                # every remaining indexed block is shared with a live
                # table: evicting gains nothing now or transitively
                return False
            pool = owned or leaves
            self._evict(min(pool, key=lambda n: n.stamp))
        return True

    def clear(self) -> None:
        def drop(node):
            for child in list(node.children.values()):
                drop(child)
            self._evict(node)
        for child in list(self._root.children.values()):
            drop(child)


@dataclasses.dataclass
class PageTable:
    """One request's pages: physical block ids in logical order.

    The first `shared_blocks` blocks are copy-on-write prefix pages,
    read-shared with the prefix index (and possibly other tables): their
    content is immutable, `store` never writes them back."""

    blocks: List[int]
    block_tokens: int
    shared_blocks: int = 0

    @property
    def capacity_tokens(self) -> int:
        return len(self.blocks) * self.block_tokens

    def padded(self, n_pages: int) -> List[int]:
        """Block-table row for the paged kernel: padded with page 0 (the
        kernel masks padded pages via valid_len)."""
        assert n_pages >= len(self.blocks), (n_pages, len(self.blocks))
        return self.blocks + [0] * (n_pages - len(self.blocks))


@dataclasses.dataclass
class KVSnapshot:
    """What an evicted request keeps besides its pages: the unpaged state
    leaves and the decode cursor, enough to resume without re-prefill."""

    state: List[Any]          # non-sequence cache leaves, flat order
    position: int             # next cache write position
    cur_token: int            # last sampled token (next decode input)


def blocks_needed(n_tokens: int, block_tokens: int) -> int:
    """Blocks covering `n_tokens` of KV (minimum one — even an empty
    request owns its first page)."""
    return max(1, math.ceil(n_tokens / block_tokens))


class PagedKVCache:
    """Block-pool storage for one engine's KV cache."""

    def __init__(self, cfg: ModelConfig, n_blocks: int, block_tokens: int,
                 max_seq: int, dtype=None, device: Optional[jax.Device] = None):
        if max_seq % block_tokens:
            raise ValueError(
                f"block_tokens={block_tokens} must divide max_seq={max_seq}")
        self.cfg = cfg
        self.block_tokens = block_tokens
        self.max_seq = max_seq
        # where the pools live; pages imported from another engine's pool
        # and resumed snapshot state are moved here
        self.device = device if device is not None else jax.devices()[0]
        self.allocator = BlockAllocator(n_blocks)
        # Probe which leaves carry the sequence axis: leaves whose shape
        # changes with max_seq are paged; the rest (recurrent states, conv
        # buffers, rolling windows < max_seq) are snapshot-wholesale state.
        shape_a = jax.eval_shape(
            lambda: M.init_cache(cfg, 1, max_seq, dtype=dtype))
        shape_b = jax.eval_shape(
            lambda: M.init_cache(cfg, 1, max_seq // 2, dtype=dtype))
        flat_a, self._treedef = jax.tree.flatten(shape_a)
        flat_b, _ = jax.tree.flatten(shape_b)
        self._seq_axis: List[Optional[int]] = []
        self._pools: List[Optional[jnp.ndarray]] = []
        for a, b in zip(flat_a, flat_b, strict=True):
            axis = next((i for i, (x, y)
                         in enumerate(zip(a.shape, b.shape, strict=True))
                         if x != y), None)
            if axis is not None and a.shape[axis] != max_seq:
                axis = None       # seq-dependent but not max_seq-sized
            self._seq_axis.append(axis)
            if axis is None:
                self._pools.append(None)
                continue
            rest = a.shape[:axis] + a.shape[axis + 1:]
            self._pools.append(jnp.zeros(
                (n_blocks, block_tokens) + rest, a.dtype,
                device=self.device))
        # prefix sharing needs the pages to BE the whole per-request
        # state: any non-sequence leaf (SSM states, rolling windows)
        # carries history the pages can't reproduce for a different
        # request, so such models keep the index off
        self.supports_prefix = bool(self._seq_axis) \
            and all(a is not None for a in self._seq_axis)
        self.prefix: Optional[PrefixIndex] = \
            PrefixIndex(self.allocator, block_tokens) \
            if self.supports_prefix else None
        self._publish = jax.jit(self.publish, donate_argnums=0)

    # ------------------------------------------------------------------
    @property
    def n_blocks(self) -> int:
        return self.allocator.n_blocks

    @property
    def free_blocks(self) -> int:
        """Allocatable blocks: truly free plus what the prefix index
        would surrender under pressure (indexed blocks no table shares).
        This is the number admission control may count on."""
        free = self.allocator.free_blocks
        if self.prefix is not None:
            free += self.prefix.reclaimable_blocks
        return free

    def blocks_for(self, n_tokens: int) -> int:
        return blocks_needed(min(n_tokens, self.max_seq), self.block_tokens)

    def _allocate_fresh(self, n: int) -> Optional[List[int]]:
        ids = self.allocator.allocate(n)
        if ids is None and self.prefix is not None \
                and self.prefix.reclaim(n):
            ids = self.allocator.allocate(n)
        return ids

    def allocate(self, n_tokens: int,
                 prompt: Optional[List[int]] = None) -> Optional[PageTable]:
        """A page table covering `n_tokens`. With `prompt` given and a
        prefix index live, resident full blocks matching the prompt's
        head are attached read-shared (`shared_blocks`) instead of being
        allocated — the caller skips that much prefill."""
        shared: List[int] = []
        if prompt is not None and self.prefix is not None:
            shared = self.match_prefix(prompt)
        if shared:
            # pin before allocating: the pressure reclaim below must not
            # evict-and-recycle the very blocks we are about to share
            self.allocator.ref(shared)
        ids = self._allocate_fresh(self.blocks_for(n_tokens) - len(shared))
        if ids is None:
            if shared:
                self.allocator.free(shared)
            return None
        return PageTable(blocks=shared + ids,
                         block_tokens=self.block_tokens,
                         shared_blocks=len(shared))

    def match_prefix(self, prompt: List[int]) -> List[int]:
        """Resident full-block ids covering `prompt`'s head ([] without
        an index). Always a strict prefix: >= 1 suffix token remains."""
        if self.prefix is None:
            return []
        return self.prefix.match(prompt)

    def fork(self, table: PageTable) -> Optional[PageTable]:
        """Copy-on-write duplicate of a live table: all but the last
        block are reference-shared; the last (still-written) block is
        copied into a fresh one. None under pool exhaustion."""
        shared = table.blocks[:-1]
        if shared:
            self.allocator.ref(shared)
        tail = self._allocate_fresh(1)
        if tail is None:
            if shared:
                self.allocator.free(shared)
            return None
        src = table.blocks[-1]
        for i, pool in enumerate(self._pools):
            if pool is not None:
                self._pools[i] = pool.at[tail[0]].set(pool[src])
        return PageTable(blocks=shared + tail,
                         block_tokens=self.block_tokens,
                         shared_blocks=len(shared))

    def free(self, table: PageTable) -> None:
        self.allocator.free(table.blocks)
        table.blocks = []
        table.shared_blocks = 0

    # ------------------------------------------------------------------
    def store(self, table: PageTable, slot_cache) -> List[Any]:
        """Scatter a dense single-slot cache into `table`'s pages.

        Only the table's `capacity_tokens` prefix of each sequence leaf is
        persisted (the request can never have written beyond it), and the
        table's leading `shared_blocks` copy-on-write pages are skipped —
        they are read-shared and already hold exactly this content.
        Returns the non-sequence state leaves for the caller's
        `KVSnapshot`."""
        flat = self._flatten(slot_cache)
        skip = table.shared_blocks
        write = table.blocks[skip:]
        ids = jnp.asarray(write, jnp.int32)
        offset = skip * self.block_tokens
        span = table.capacity_tokens
        state: List[Any] = []
        for i, leaf in enumerate(flat):
            axis = self._seq_axis[i]
            if axis is None:
                state.append(leaf)
                continue
            if not write:
                continue
            lead = jnp.moveaxis(leaf, axis, 0)[offset:span]
            pages = lead.reshape((len(write), self.block_tokens)
                                 + lead.shape[1:])
            self._pools[i] = self._pools[i].at[ids].set(pages)
        return state

    @property
    def pools(self) -> List[Optional[Any]]:
        """The pool leaves in the cache tree's flat order (None for a
        leaf with no sequence axis)."""
        return self._pools

    @pools.setter
    def pools(self, pools: List[Optional[Any]]) -> None:
        self._pools = list(pools)

    def publish_ids(self, table: PageTable, n_tokens: int,
                    n_pages: int) -> np.ndarray:
        """`n_pages` page ids for `publish`: entry k is `table`'s block k
        for each full block of the first `n_tokens` past the read-shared
        head, and `n_blocks` (out of range, so dropped) elsewhere."""
        n_full = min(len(table.blocks), n_tokens // self.block_tokens,
                     n_pages)
        skip = table.shared_blocks
        ids = np.full(n_pages, self.n_blocks, np.int32)
        ids[skip:n_full] = table.blocks[skip:n_full]
        return ids

    def publish(self, pools: List[Optional[Any]], slot_cache, ids):
        """`pools` with block k of the dense single-slot `slot_cache` (its
        tokens `[k * block_tokens, (k + 1) * block_tokens)`) written to
        page `ids[k]`, for every k < len(ids); an out-of-range id drops
        its block. Pure, so it traces into a program that donates
        `pools`; the shapes depend only on len(ids). Each page is its own
        in-place slice update: a scatter would have XLA relayout (copy)
        the whole pool around it on TPU."""
        bt = self.block_tokens
        out = []
        for pool, leaf, axis in zip(pools, self._flatten(slot_cache),
                                    self._seq_axis, strict=True):
            if axis is None or ids.shape[0] == 0:
                out.append(pool)
                continue

            def write(k, pool, leaf=leaf, axis=axis):
                block = jax.lax.dynamic_slice_in_dim(leaf, k * bt, bt, axis)
                page = jnp.moveaxis(block, axis, 0)[None]
                return jax.lax.cond(
                    ids[k] < pool.shape[0],
                    lambda p: jax.lax.dynamic_update_slice_in_dim(
                        p, page, ids[k], 0),
                    lambda p: p, pool)
            out.append(jax.lax.fori_loop(0, ids.shape[0], write, pool))
        return out

    def store_prefix(self, table: PageTable, slot_cache,
                     n_tokens: int) -> None:
        """Persist a live slot's *full* blocks (the first
        `n_tokens // block_tokens` pages, minus the read-shared head)
        into the pool, through `publish` with the pools donated — so
        `register_prefix` publishes pages that actually hold the prompt's
        KV (ordinarily pages are only written at eviction)."""
        ids = self.publish_ids(table, n_tokens,
                               self.max_seq // self.block_tokens)
        if (ids < self.n_blocks).any():
            self._pools = self._publish(
                self._pools, slot_cache, jax.device_put(ids, self.device))

    def register_prefix(self, prompt: List[int],
                        table: PageTable) -> None:
        """Publish a prefilled prompt's full blocks to the prefix index
        (no-op without one). Call once its pages are written
        (`store_prefix`, or `publish` in the engine's admission)."""
        if self.prefix is not None:
            self.prefix.register(prompt, table.blocks)

    # ------------------------------------------------------------------
    def export(self, table: PageTable) -> List[Optional[Any]]:
        """The table's page contents, one `(n_blocks, block_tokens,
        *rest)` array per sequence leaf (None for non-sequence leaves) —
        the wire format of a KV migration."""
        ids = jnp.asarray(table.blocks, jnp.int32)
        return [None if pool is None else pool[ids]
                for pool in self._pools]

    def import_pages(self, pages: List[Optional[Any]],
                     n_blocks: int) -> Optional[PageTable]:
        """Adopt migrated pages into this pool: allocate `n_blocks`
        fresh blocks, move each exported leaf to this pool's device and
        scatter it in. None under pool exhaustion (the caller falls back
        to re-prefill)."""
        ids = self._allocate_fresh(n_blocks)
        if ids is None:
            return None
        arr = jnp.asarray(ids, jnp.int32)
        for i, leaf in enumerate(pages):
            if leaf is None:
                continue
            self._pools[i] = self._pools[i].at[arr].set(
                jax.device_put(leaf, self.device))
        return PageTable(blocks=ids, block_tokens=self.block_tokens)

    def load(self, table: PageTable, state: List[Any]):
        """Gather `table`'s pages back into a dense single-slot cache.

        Sequence positions past the table's span are zeros; decode masks
        them by position exactly as it masks never-written tail slots.
        State leaves are moved to this pool's device (a migrated snapshot
        arrives from another engine's)."""
        ids = jnp.asarray(table.blocks, jnp.int32)
        flat: List[Any] = []
        state_it = iter(state)
        for i, axis in enumerate(self._seq_axis):
            if axis is None:
                flat.append(jax.device_put(next(state_it), self.device))
                continue
            pool = self._pools[i]
            pages = pool[ids]                       # (nb, bt, *rest)
            lead = pages.reshape((-1,) + pages.shape[2:])
            rest = pool.shape[2:]
            full = jnp.zeros((self.max_seq,) + rest, pool.dtype,
                             device=self.device)
            full = full.at[: lead.shape[0]].set(lead)
            flat.append(jnp.moveaxis(full, 0, axis))
        return jax.tree.unflatten(self._treedef, flat)

    def _flatten(self, slot_cache) -> List[Any]:
        flat, treedef = jax.tree.flatten(slot_cache)
        if treedef != self._treedef:
            raise ValueError(
                f"slot cache tree {treedef} does not match the pool's "
                f"{self._treedef}")
        return flat
