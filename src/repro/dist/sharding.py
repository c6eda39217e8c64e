"""Sharding rules + mesh plumbing (single-device shim with full-mesh semantics).

``shard(x, *entries)`` applies a per-dimension sharding constraint when a mesh
is active (installed via ``use_mesh``) and is the identity otherwise, so model
code is written once for the production (pod, data, model) mesh and still runs
on a laptop CPU. Entries are mesh-axis names, tuples of names (an axis group
like ``("pod", "data")``), or None (replicated); axes absent from the active
mesh are dropped at resolution time, which is how the 2-axis host mesh and the
3-axis multi-pod mesh share one rule set.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Iterable, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Entry = Union[None, str, Tuple[str, ...]]

_state = threading.local()


def get_mesh() -> Optional[Mesh]:
    """The mesh installed by the innermost ``use_mesh``, or None."""
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Install ``mesh`` as the active mesh for ``shard``/``get_mesh``."""
    prev = get_mesh()
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


def _resolve_entry(e: Entry, axes: Iterable[str]) -> Entry:
    """Drop mesh axes not present in ``axes``; collapse singleton tuples."""
    if e is None:
        return None
    axes = set(axes)
    names = (e,) if isinstance(e, str) else tuple(e)
    present = tuple(n for n in names if n in axes)
    if not present:
        return None
    if len(present) == 1:
        return present[0]
    return present


def shard(x: Any, *entries: Entry) -> Any:
    """Constrain ``x``'s sharding per dimension under the active mesh.

    No-op when no mesh is active (single-device paths, unit tests)."""
    mesh = get_mesh()
    if mesh is None:
        return x
    axes = set(mesh.axis_names)
    spec = P(*[_resolve_entry(e, axes) for e in entries])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_spec(mesh: Mesh, *trailing: Entry) -> NamedSharding:
    """Sharding for a host batch: leading (batch) dim over the data axes."""
    axes = set(mesh.axis_names)
    lead = _resolve_entry(("pod", "data"), axes)
    return NamedSharding(
        mesh, P(lead, *[_resolve_entry(e, axes) for e in trailing]))


# ---------------------------------------------------------------------------
# parameter placement rules
# ---------------------------------------------------------------------------

_NORM_LEAVES = ("norm", "scale", "bias", "gamma", "beta")


def param_spec(path: str, ndim: int) -> P:
    """PartitionSpec for a parameter by its flat path + rank.

    Rules (megatron-style tensor parallelism + data-parallel ZeRO over the
    reduce dimension):
      * norm / scale / bias leaves: replicated;
      * embeddings: vocab over ``model``, feature over ``data``;
      * MoE expert weights (rank >= 3 under a moe/expert layer): experts over
        ``model``, the contracting dim over ``data``;
      * generic matmul weights: contracting dim over ``data``, output dim
        over ``model``; leading (stacked-layer) dims replicated.
    """
    leaf = path.rsplit("/", 1)[-1]
    if leaf.startswith("ln") or any(tag in leaf for tag in _NORM_LEAVES):
        return P(*([None] * ndim))
    if ndim <= 1:
        return P(*([None] * ndim))
    if "embed" in path:
        return P("model", "data", *([None] * (ndim - 2)))
    if ("moe" in path or "expert" in path) and ndim >= 3:
        return P(*([None] * (ndim - 3)), "model", "data", None)
    return P(*([None] * (ndim - 2)), "data", "model")


def state_shardings(mesh: Mesh, state: Any) -> Any:
    """A ``NamedSharding`` per leaf of a train-state pytree (arrays or
    shape structs), placed by :func:`param_spec` of the leaf's
    "/"-joined path — optimizer moments follow their parameter's rule."""
    def one(path, leaf):
        key = jax.tree_util.keystr(path, simple=True, separator="/")
        return NamedSharding(mesh, param_spec(key, len(leaf.shape)))
    return jax.tree_util.tree_map_with_path(one, state)


def shard_cuts(path: str, shape, itemsize: int,
               n_shards: int) -> Optional[list]:
    """Byte offsets where ``n_shards`` axis-0 shards of this param begin/end.

    The chunk layer (``store/chunks.py``, DESIGN.md §12) uses these as hard
    segment boundaries so no chunk straddles two shards — each host of a
    distributed consumer can then pull exactly the chunk set covering its
    own shard. Only axis-0 sharding produces *contiguous* byte ranges in a
    C-order tensor, so cuts exist only when :func:`param_spec` shards
    dimension 0 (2-D matmul weights shard dim 0 over ``data``, embeddings
    over ``model``); replicated or inner-dim-only placements return None.
    """
    shape = tuple(int(d) for d in shape)
    if n_shards <= 1 or len(shape) < 2:
        return None
    spec = param_spec(path, len(shape))
    if not tuple(spec) or tuple(spec)[0] is None:
        return None
    rows = shape[0]
    if rows < n_shards:
        return None
    row_bytes = itemsize
    for d in shape[1:]:
        row_bytes *= d
    # same split arithmetic as jax's even-ceil sharding over axis 0
    cuts = []
    for s in range(1, n_shards):
        cuts.append((s * rows) // n_shards * row_bytes)
    return cuts
