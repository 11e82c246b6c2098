"""Tiered arrays — paper §4.1 data partition (Fig. 5a).

A matrix operand is split along one axis into a *local* part and a
*remote* part.  Weights split along the output-row (M) dimension; KV caches
split along batch (decode) or sequence (long-context split-K).

Both parts are device (HBM) arrays today.  The paper reads the remote part
from host memory directly, but on v5e with JAX 0.9 / libtpu 0.0.34 a Pallas
operand in host memory does not compile (`kernels.splitk_gemm` has the
details), so the remote tier is a separate HBM buffer that the kernels and
the traffic model (`core/ebmodel.py`) treat as host-resident.
`TieredArray` is a pytree, so it flows through jit/pjit/scan unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def split_sizes(dim: int, ratio: float, align: int = 1) -> tuple[int, int]:
    """(local_rows, remote_rows): remote ≈ ratio·dim rounded to `align`.

    Paper §4.1 "execution wave alignment": tile rows are sized so each
    partition is a whole number of kernel tiles.
    """
    if not 0.0 <= ratio <= 1.0:
        raise ValueError(f"ratio must be in [0,1], got {ratio}")
    remote = int(round(dim * ratio / align)) * align
    remote = min(remote, (dim // align) * align if align > 1 else dim)
    return dim - remote, remote


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TieredArray:
    """An operand partitioned across (local HBM, remote host) tiers.

    ``mesh_axes`` marks a *mesh-sharded* remote tier: the host partition is
    laid out as disjoint 1/P slices along `axis`, one per device of the
    named mesh axis (each chip's slice is what its own host link streams —
    paper §4.3.2).  A sharded operand must be rebuilt by the fetch-once
    broadcast (`kernels.ops.broadcast_remote` inside ``shard_map``) before
    the tier-aware compute ops consume it; ``mesh_axes is None`` (the
    default, and the state after a fetch) means the remote tier is whole.
    """

    local: jax.Array            # rows [0, split) along `axis`
    remote: jax.Array           # rows [split, dim) along `axis`
    axis: int = 0
    mesh_axes: str | None = None   # mesh axis sharding `remote` (None = whole)

    def tree_flatten(self) -> tuple[tuple[jax.Array, jax.Array],
                                    tuple[int, str | None]]:
        return (self.local, self.remote), (self.axis, self.mesh_axes)

    @classmethod
    def tree_unflatten(cls, aux, children) -> "TieredArray":
        return cls(children[0], children[1], axis=aux[0],
                   mesh_axes=aux[1] if len(aux) > 1 else None)

    # -- convenience ------------------------------------------------------
    @property
    def shape(self) -> tuple[int, ...]:
        s = list(self.local.shape)
        s[self.axis] += self.remote.shape[self.axis]
        return tuple(s)

    @property
    def dtype(self) -> jnp.dtype:
        return self.local.dtype

    @property
    def ratio(self) -> float:
        d = self.shape[self.axis]
        return self.remote.shape[self.axis] / d if d else 0.0

    @property
    def nbytes(self) -> int:
        return int(self.local.size * self.local.dtype.itemsize
                   + self.remote.size * self.remote.dtype.itemsize)

    def materialize(self) -> jax.Array:
        """Concatenate tiers (reference semantics; tests/oracles only)."""
        return jnp.concatenate([self.local, self.remote], axis=self.axis)


def partition(x: jax.Array, ratio: float, axis: int = 0, align: int = 1) -> TieredArray:
    """Split `x` along `axis`: trailing `ratio` fraction goes to the host tier.

    Negative axes are supported (and preferred by the operand registry —
    `models.registry`): a negative split axis stays valid when a leading
    stacking axis is peeled off by ``jax.lax.scan`` or a per-layer slice.
    """
    dim = x.shape[axis]
    n_local, n_remote = split_sizes(dim, ratio, align)
    local, remote = jnp.split(x, [n_local], axis=axis)
    return TieredArray(local=local, remote=remote, axis=axis)


def matmul(x: jax.Array, w: Any) -> jax.Array:
    """``x @ w`` with operand-type dispatch on tiered weights.

    The unified tiering API's reference-semantics compute op: plain arrays
    pass straight through to ``@``; a column-split `TieredArray` computes
    each tier from its own buffer and concatenates the outputs — on a real
    runtime the remote matmul streams its operand over the host link (the
    `SplitK_GEMM` kernel in `kernels.ops.tiered_matmul` is the direct-access
    realization of the same contraction).  Used throughout `models.layers`
    so every model family's forward/prefill/decode accepts tiered params.
    """
    if isinstance(w, TieredArray):
        if w.axis not in (-1, w.local.ndim - 1):
            raise ValueError(
                f"tier-aware matmul supports column-split operands only "
                f"(axis=-1), got axis={w.axis} for shape {w.shape}")
        return jnp.concatenate([x @ w.local, x @ w.remote], axis=-1)
    return x @ w


def partition_tree(
    params: Any, ratios: dict[str, float], align: int = 1, axis: int = 0
) -> Any:
    """Partition every param whose path matches a ratio entry.

    .. deprecated::
        Path-pattern partitioning predates the operand registry; use
        ``TieringPlan.partition`` (`core.engine`), which resolves leaves,
        split axes, and alignment from `models.registry.operand_registry`.
        Kept for one release as a low-level escape hatch.

    `ratios` maps '/'-joined key-paths (as produced by
    ``jax.tree_util.keystr``-lite below) to offload ratios. Params without a
    matching entry stay untouched (ratio 0 == fully local, no wrapper).
    """

    def path_str(path) -> str:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            else:
                parts.append(str(p))
        return "/".join(parts)

    def maybe_split(path, leaf):
        r = ratios.get(path_str(path))
        if r is None or r <= 0.0 or not hasattr(leaf, "shape") or leaf.ndim < 2:
            return leaf
        return partition(leaf, r, axis=axis, align=align)

    return jax.tree_util.tree_map_with_path(maybe_split, params)


def traffic_bytes(t: TieredArray) -> tuple[int, int]:
    """(local_bytes, remote_bytes) fetched by one full read of the operand."""
    return (
        int(t.local.size * t.local.dtype.itemsize),
        int(t.remote.size * t.remote.dtype.itemsize),
    )


def validate(t: TieredArray) -> None:
    """Invariants checked by property tests."""
    assert t.local.dtype == t.remote.dtype, "tier dtype mismatch"
    ls, rs = list(t.local.shape), list(t.remote.shape)
    ls.pop(t.axis), rs.pop(t.axis)
    assert ls == rs, f"non-split dims must match: {t.local.shape} vs {t.remote.shape}"


def as_numpy_pair(t: TieredArray) -> tuple[np.ndarray, np.ndarray]:
    return np.asarray(t.local), np.asarray(t.remote)
