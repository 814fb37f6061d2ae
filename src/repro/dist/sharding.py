"""Logical-axis -> PartitionSpec rules engine.

Model code never names mesh axes.  It tags tensor dims with *logical* names
("batch", "embed", "kv_heads", ...) and this module resolves them against
whatever mesh is active: the 16x16 production pod, the 2x16x16 multi-pod
mesh, a 4x2 host mesh in tests, or no mesh at all (``shard`` is then a
no-op) — one model codebase, every deployment shape.

Resolution walks the tensor dims left to right.  For each logical name,
``RULES`` lists candidate mesh axes in priority order (a candidate may merge
several axes, e.g. batch over ``("pod", "data")`` on multi-pod meshes).  A
candidate is taken only if every axis exists in the mesh, none is already
used by an earlier dim of the SAME tensor, and the combined axis size
divides the dim; otherwise the next candidate is tried, else the dim
replicates.  Divisibility doubles as the fallback mechanism, e.g. 10 kv
heads on a 16-way model axis leave the axis free so "head_dim" (128) picks
it up — the KV layout the serving cache relies on — and size-1 dims always
replicate (1 is divisible by nothing > 1).

``override_rules`` swaps rules thread-locally for perf experiments
(benchmarks/perf_iter.py sweeps e.g. ``embed=()`` = pure tensor-parallel
serving with replicated embeddings).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.interpreters import pxla
from jax.sharding import NamedSharding, PartitionSpec as P

# logical name -> candidates, tried in order; each candidate is one mesh
# axis or a tuple of mesh axes sharded jointly.  () = always replicate.
RULES: Dict[str, Tuple[Any, ...]] = {
    "batch":    (("pod", "data"), "data"),   # data parallel; pods merge
    "seq":      (),                          # sequence stays local
    "seq_sp":   ("model",),                  # Megatron-style seq parallel
    "embed":    ("data",),                   # FSDP: params shard over data
    "vocab":    ("model",),                  # tensor-parallel (un)embedding
    "heads":    ("model",),
    "kv_heads": ("model",),
    "head_dim": ("model",),                  # KV fallback when kv_heads ∤
    "mlp":      ("model",),
    "state":    ("model",),                  # ssd / rg-lru widths
    "experts":  ("model",),                  # expert-parallel shard dim
    "layers":   (),                          # lax.scan stacked-layer axis
    "none":     (),
}

_local = threading.local()


def _active_rules() -> Dict[str, Tuple[Any, ...]]:
    over = getattr(_local, "overrides", None)
    if not over:
        return RULES
    merged = dict(RULES)
    merged.update(over)
    return merged


def _as_candidates(value) -> Tuple[Any, ...]:
    """Accept "model", ("model",), (("pod","data"), "data"), or ()."""
    if value is None:
        return ()
    if isinstance(value, str):
        return (value,)
    return tuple(value)


@contextlib.contextmanager
def override_rules(**overrides):
    """Thread-locally replace rule entries, e.g. ``override_rules(embed=())``
    to replicate embeddings.  Nests; restores the previous state on exit."""
    prev = getattr(_local, "overrides", None)
    merged = dict(prev or {})
    merged.update({k: _as_candidates(v) for k, v in overrides.items()})
    _local.overrides = merged
    try:
        yield
    finally:
        _local.overrides = prev


def current_mesh():
    """The mesh entered via ``with mesh:``, or None outside any mesh."""
    mesh = pxla.thread_resources.env.physical_mesh
    if mesh is None or mesh.empty:
        return None
    return mesh


def spec_for(shape: Sequence[int], names: Sequence[Optional[str]],
             mesh=None) -> P:
    """Resolve logical ``names`` for a tensor of ``shape`` into a
    PartitionSpec on ``mesh`` (anything with a ``.shape`` axis->size
    mapping).  No mesh axis is assigned twice within one tensor."""
    mesh = mesh if mesh is not None else current_mesh()
    sizes = dict(mesh.shape) if mesh is not None else {}
    rules = _active_rules()
    if len(names) > len(shape):
        raise ValueError(f"{len(names)} logical names {tuple(names)} for a "
                         f"rank-{len(shape)} tensor of shape {tuple(shape)}")
    names = tuple(names) + (None,) * (len(shape) - len(names))
    used: set = set()
    entries = []
    for dim, name in zip(shape, names):
        entry = None
        for cand in rules.get(name or "none", ()):
            axes = (cand,) if isinstance(cand, str) else tuple(cand)
            if not all(a in sizes for a in axes):
                continue
            if any(a in used for a in axes):
                continue
            n = 1
            for a in axes:
                n *= sizes[a]
            if n <= 1 or dim % n != 0:
                continue
            entry = axes[0] if len(axes) == 1 else axes
            used.update(axes)
            break
        entries.append(entry)
    return P(*entries)


def shard(x, *names):
    """Constraint-annotate ``x`` with the resolved spec for ``names`` under
    the active mesh; identity when no mesh is active (single-host paths,
    unit tests) so model code can call it unconditionally."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = spec_for(x.shape, names, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh, shape: Sequence[int],
                   names: Sequence[Optional[str]]) -> NamedSharding:
    return NamedSharding(mesh, spec_for(shape, names, mesh))


def tree_shardings(mesh, tree, spec_tree):
    """NamedSharding pytree for ``tree`` (arrays or ShapeDtypeStructs).
    ``spec_tree`` mirrors ``tree`` with tuples of logical names at the
    leaves (the ``param_spec`` / ``cache_spec`` convention)."""
    treedef = jax.tree.structure(tree)
    leaves = jax.tree.leaves(tree)
    specs = treedef.flatten_up_to(spec_tree)
    shardings = [NamedSharding(mesh, spec_for(leaf.shape, names, mesh))
                 for leaf, names in zip(leaves, specs)]
    return jax.tree.unflatten(treedef, shardings)
