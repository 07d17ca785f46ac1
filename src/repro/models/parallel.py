"""Parallelism context: mesh-aware sharding helpers shared by all models.

All model code is written against a ``ParallelContext``. With ``mesh=None``
(CPU smoke tests) every helper is a no-op; under the production mesh the same
code paths emit explicit ``with_sharding_constraint``s, so the single model
definition serves 1-device tests and the 512-chip dry-run alike.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    mesh: Optional[Mesh] = None
    batch_axes: Tuple[str, ...] = ("data",)   # ("pod","data") on multi-pod
    model_axis: Optional[str] = "model"
    cp_axis: Optional[str] = None   # context-parallel axis for long-KV decode
    # Pallas kernels lower to Mosaic on a TPU; on the CPU they run in
    # interpret mode, which is for the CPU tests only
    use_pallas: bool = False
    remat: bool = True              # activation checkpointing in train_step
    moe_expert_parallel: bool = False  # §Perf layout lever (EXPERIMENTS.md)
    moe_dispatch: str = "dense"        # dense | capacity (§Perf lever)

    # ------------------------------------------------------------------
    def axis_size(self, name: Optional[str]) -> int:
        if self.mesh is None or name is None:
            return 1
        return self.mesh.shape[name]

    @property
    def batch_size_divisor(self) -> int:
        out = 1
        for a in self.batch_axes:
            out *= self.axis_size(a)
        return out

    def sharding(self, *spec) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(*spec))

    def constrain(self, x, *spec):
        """with_sharding_constraint; no-op when there is no mesh."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))

    # shorthand specs -----------------------------------------------------
    @property
    def batch_spec(self):
        """Spec entry that shards a batch dimension."""
        return self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0]

    def model_spec_if(self, dim_size: int):
        """'model' if dim divisible by the model-axis size, else None."""
        m = self.axis_size(self.model_axis)
        if m > 1 and dim_size % m == 0:
            return self.model_axis
        return None

    def shard_batch(self, x):
        """Shard the leading (batch) dim; replicate the rest."""
        if self.mesh is None:
            return x
        bsz = x.shape[0]
        spec = [None] * x.ndim
        if bsz % self.batch_size_divisor == 0:
            spec[0] = self.batch_spec
        return self.constrain(x, *spec)

    def shard_activation(self, x):
        """(B, S, D) activations at residual boundaries.

        Batch over the data axes; sequence over the model axis when it
        divides (Megatron-style sequence parallelism) — the residual stream
        saved per scanned layer for backward then costs 1/|model| of the
        replicated footprint. GSPMD inserts the all-gather at each layer's
        first matmul and the reduce-scatter after the residual add.
        """
        if self.mesh is None:
            return x
        spec = [None] * x.ndim
        if x.shape[0] % self.batch_size_divisor == 0:
            spec[0] = self.batch_spec
        m = self.axis_size(self.model_axis)
        if (x.ndim == 3 and m > 1 and x.shape[1] > 1
                and x.shape[1] % m == 0):
            spec[1] = self.model_axis
        return self.constrain(x, *spec)


def cpu_context(**kw) -> ParallelContext:
    return ParallelContext(mesh=None, batch_axes=(), model_axis=None, **kw)


# ---------------------------------------------------------------------------
# Name-based parameter sharding rules (tensor parallelism over "model")
# ---------------------------------------------------------------------------

# Each rule: (leaf-name, ndim) -> index of the dim sharded over "model".
# Column-parallel projections shard their output dim; row-parallel their
# input dim, so matmul chains avoid resharding (Megatron layout).
_COL_PARALLEL = {
    "wq", "wk", "wv", "w_up", "w_gate", "wq_b", "wkv_b", "wx", "wz",
    "w_rec_in", "w_gate_in",
}
_ROW_PARALLEL = {"wo", "w_down", "out_proj"}
_VOCAB_PARALLEL = {"embed", "unembed"}
_EXPERT_STACKED_COL = {"we_up", "we_gate"}   # (E, D, F): shard F
_EXPERT_STACKED_ROW = {"we_down"}            # (E, F, D): shard F


def spec_for_param(path: Sequence, leaf) -> P:
    """PartitionSpec for one parameter leaf, by name + rank.

    Divisibility is NOT checked here — ``apply_param_specs`` downgrades any
    non-divisible entry to replication against a concrete mesh.
    """
    name = None
    for entry in reversed(path):
        if hasattr(entry, "key"):
            name = entry.key
            break
        if hasattr(entry, "name"):
            name = entry.name
            break
    nd = leaf.ndim if hasattr(leaf, "ndim") else 0
    if name is None or nd == 0:
        return P()
    spec = [None] * nd
    if name in _VOCAB_PARALLEL and nd >= 2:
        spec[nd - 2] = "model"
    elif name in _COL_PARALLEL:
        spec[nd - 1] = "model"
    elif name in _ROW_PARALLEL:
        spec[nd - 2] = "model"
    elif name in _EXPERT_STACKED_COL:
        spec[nd - 1] = "model"
    elif name in _EXPERT_STACKED_ROW:
        spec[nd - 2] = "model"
    return P(*spec)


def param_specs(params_shapes, ctx: ParallelContext):
    """Tree of PartitionSpecs matching a params(-shapes) pytree."""

    def fix(path, leaf):
        spec = spec_for_param(path, leaf)
        if ctx.mesh is None:
            return P()
        out = []
        for dim, entry in enumerate(spec):
            if entry is None:
                out.append(None)
            else:
                ax = ctx.axis_size(entry)
                out.append(entry if leaf.shape[dim] % ax == 0 else None)
        # pad (P() shorter than rank is fine, but keep explicit)
        while len(out) < leaf.ndim:
            out.append(None)
        return P(*out)

    return jax.tree_util.tree_map_with_path(fix, params_shapes)


def param_shardings(params_shapes, ctx: ParallelContext):
    specs = param_specs(params_shapes, ctx)
    if ctx.mesh is None:
        return specs
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def opt_state_shardings(params_shapes, ctx: ParallelContext):
    """ZeRO-1-style specs for optimizer moments: the tensor-parallel param
    spec plus the data axes on the first additionally-divisible dim. Adam
    math is elementwise, so moments never need gathering — only the final
    param delta is resharded (one all-gather per step)."""
    specs = param_specs(params_shapes, ctx)
    if ctx.mesh is None:
        return specs
    dsize = 1
    for a in ctx.batch_axes:
        dsize *= ctx.axis_size(a)

    def widen(path, leaf):
        spec = list(_lookup_spec(specs, path))
        while len(spec) < leaf.ndim:
            spec.append(None)
        for dim in range(leaf.ndim):
            if spec[dim] is None and leaf.shape[dim] % dsize == 0 \
                    and leaf.shape[dim] >= dsize:
                spec[dim] = self_batch = ctx.batch_spec
                break
        return NamedSharding(ctx.mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(widen, params_shapes)


def _lookup_spec(specs, path):
    node = specs
    for entry in path:
        key = getattr(entry, "key", getattr(entry, "idx", None))
        node = node[key]
    return node
