"""Logical-axis sharding rules (GSPMD annotations for every code path).

Models annotate tensors with *logical* axis names ("batch", "heads",
"ff", ...); a rule table maps each logical name to zero or more *mesh*
axes.  One table per execution regime:

* :data:`DEFAULT_RULES`        — training / calibration: batch+FSDP over
  ``(pod, data)``, tensor-parallel weights over ``model``.
* :data:`SERVE_PREFILL_RULES`  — prefill additionally sequence-shards
  activations over ``model`` (long prompts; weight layout unchanged).
* :data:`SERVE_DECODE_RULES`   — the 2D-TP decode layout: weights split
  over (data=input-dim, model=output-dim); ``qin: None`` is the explicit
  opt-in marker for the packed-domain transfer constraint in
  :func:`repro.kernels.ops.quant_matmul` (see DESIGN.md §6.1).

The mapping is *best-effort by construction* (DESIGN.md §6.1): a rule is
dropped for a given tensor dimension when the mesh axis is absent from
the active mesh, already used by an earlier dimension of the same tensor
(each mesh axis at most once per spec, earlier dims win), or does not
divide the dimension size (replicate rather than pad).  This is what
lets one model definition lower on a 16x16 pod, a 2x16x16 twin-pod, 8
virtual CPU devices, or a single CPU without edits.
"""
from __future__ import annotations

import contextlib
import logging
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import _tree

logger = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------

DEFAULT_RULES = {
    # data / activation axes
    "batch":    ("pod", "data"),
    "seq":      None,
    "embed":    None,
    # weight / head axes (tensor parallel)
    "heads":    "model",
    "kv_heads": "model",
    "kv_seq":   "model",     # fallback when the head count doesn't divide
    "ff":       "model",
    "vocab":    "model",
    "expert":   "model",
    "experts":  "model",     # stacked expert dim in MoE param trees
    "fsdp":     ("pod", "data"),
    # QuantizedTensor children (non-None here = packed-domain constraint
    # in kernels/ops.py stays OFF; see SERVE_DECODE_RULES)
    "qin":      ("pod", "data"),
    "qout":     "model",
    "qgroups":  None,
}

SERVE_PREFILL_RULES = dict(DEFAULT_RULES, seq="model")

SERVE_DECODE_RULES = dict(
    DEFAULT_RULES,
    # qin=None REPLICATES the packed input dim — it is deliberately not
    # "data": kernels/ops.py treats a None "qin" rule as the explicit
    # opt-in to constrain packed weights so cross-device movement happens
    # in the uint8 domain (mapping qin to a mesh axis would turn that
    # branch off, not shard the weights harder).
    qin=None,
)


# ---------------------------------------------------------------------------
# Active-context machinery
# ---------------------------------------------------------------------------

_CTX = threading.local()


def _stack():
    if not hasattr(_CTX, "stack"):
        _CTX.stack = []
    return _CTX.stack


@contextlib.contextmanager
def axis_rules(mesh, rules: Optional[dict] = None):
    """Activate ``(mesh, rules)`` for :func:`shard_hint` /
    :func:`active_rule` in this thread.  Nestable; inner wins."""
    _stack().append((mesh, DEFAULT_RULES if rules is None else rules))
    try:
        yield mesh
    finally:
        _stack().pop()


def active_mesh():
    """The mesh of the innermost :func:`axis_rules` context (or None)."""
    s = _stack()
    return s[-1][0] if s else None


def active_rules() -> dict:
    s = _stack()
    return s[-1][1] if s else DEFAULT_RULES


def active_rule(name: str):
    """The mesh-axis mapping the active rule table gives ``name``."""
    return active_rules().get(name)


@contextlib.contextmanager
def row_parallel():
    """Mark a region whose quantized matmuls are *row-parallel* (weight
    sharded on the input dim, e.g. attention ``wo`` / MLP ``w_down``).

    Under :data:`SERVE_DECODE_RULES` the ``qin: None`` rule arms the
    packed-domain transfer constraint in :func:`repro.kernels.ops
    .quant_matmul`, which forces a *column* layout ``P(None, "model")``
    on every 2-D codes tensor.  For row-parallel sites that layout
    contradicts the placement chosen from ``param_axes()`` and would
    insert a per-layer weight reshard.  Re-binding ``qin`` to ``model``
    inside this context disarms the branch (the rule is no longer None)
    and matches the actual row layout.  The kernel dispatch reads the
    mark itself through :func:`in_row_parallel` to pick its shard_map
    layout.
    """
    _CTX.row = getattr(_CTX, "row", 0) + 1
    try:
        mesh = active_mesh()
        if mesh is None or active_rule("qin") is not None:
            yield
        else:
            with axis_rules(mesh, dict(active_rules(), qin="model")):
                yield
    finally:
        _CTX.row -= 1


def in_row_parallel() -> bool:
    """True inside a :func:`row_parallel` region."""
    return getattr(_CTX, "row", 0) > 0


# ---------------------------------------------------------------------------
# Logical axes -> PartitionSpec
# ---------------------------------------------------------------------------

def _mesh_axis_sizes(mesh) -> dict:
    # jax.sharding.Mesh.shape is an OrderedDict {axis: size}; tests use a
    # duck-typed stand-in with a plain dict.
    return dict(mesh.shape)


# Divisibility fallbacks already warned about, keyed on
# (axes, shape, dim, logical name, dropped mesh axes) — silent
# replication during serve should show up in logs exactly once per
# distinct site.  The logical name is part of the key: two sites that
# agree on position and shape but drop a *different* logical axis are
# different warnings, and must not mask each other.
_WARNED_DROPS: set = set()


def _warn_dropped(axes, shape, dim, name, cand, total):
    if shape[dim] == 1:
        return  # replicating a singleton dim loses nothing
    key = (tuple(axes), tuple(shape), dim, name, cand)
    if key in _WARNED_DROPS:
        return
    _WARNED_DROPS.add(key)
    logger.warning(
        "logical_to_spec: replicating dim %d (logical %r, size %d) of "
        "shape %s — mesh axes %s have total size %d which does not divide "
        "it; tensor stays correct but this site is NOT sharded",
        dim, name, shape[dim], tuple(shape), cand, total)


def logical_to_spec(axes: Sequence[Optional[str]], *, shape: Sequence[int],
                    mesh, rules: Optional[dict] = None) -> P:
    """Map per-dimension logical names to a PartitionSpec on ``mesh``.

    ``axes[i]`` names dimension ``i`` of a tensor with concrete ``shape``;
    ``None`` entries replicate.  Rule entries may name one mesh axis or a
    tuple of mesh axes (sharded over their product).  Fallbacks, in order:
    mesh axes absent from ``mesh`` are dropped; mesh axes already claimed
    by an earlier dimension are dropped (each-axis-used-once priority);
    if the surviving axes' product doesn't divide ``shape[i]``, the
    dimension replicates.
    """
    rules = active_rules() if rules is None else rules
    sizes = _mesh_axis_sizes(mesh)
    used: set = set()
    entries = []
    for dim, name in enumerate(axes):
        rule = rules.get(name) if name is not None else None
        if rule is None:
            entries.append(None)
            continue
        cand = (rule,) if isinstance(rule, str) else tuple(rule)
        cand = tuple(a for a in cand if a in sizes and a not in used)
        if not cand:
            entries.append(None)
            continue
        total = 1
        for a in cand:
            total *= sizes[a]
        if shape[dim] % total != 0:
            _warn_dropped(axes, shape, dim, name, cand, total)
            entries.append(None)
            continue
        used.update(cand)
        entries.append(cand[0] if len(cand) == 1 else cand)
    return P(*entries)


def shard_hint(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """``with_sharding_constraint`` under the active mesh; identity when no
    mesh is active (single-process CPU runs, shard_map bodies, tests)."""
    mesh = active_mesh()
    if mesh is None or x.ndim != len(axes):
        return x
    spec = logical_to_spec(axes, shape=x.shape, mesh=mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Tree-level shardings
# ---------------------------------------------------------------------------

def _axes_at(axes_tree, path):
    """Walk a nested axes tree along a pytree key path; the first
    tuple/list hit is the leaf annotation (stacked-layer params share
    one annotation per site), anything else means 'replicate'."""
    node = _tree.descend(axes_tree, path,
                         lambda n: isinstance(n, (tuple, list)))
    return node if isinstance(node, (tuple, list)) else None


def tree_shardings(mesh, specs, axes_tree, rules: Optional[dict] = None):
    """NamedSharding tree for ``specs`` (arrays or ShapeDtypeStructs) from
    a matching tree of per-dimension logical-axis annotations.

    Paths absent from ``axes_tree`` (or annotated ``None``) replicate.
    Annotations shorter/longer than the leaf rank are padded/truncated
    with ``None`` so scalar extras ("len", "step") never error.
    """
    def one(path, leaf):
        ax = _axes_at(axes_tree, path)
        if ax is None:
            return NamedSharding(mesh, P())
        ax = list(ax)[:len(leaf.shape)]
        ax += [None] * (len(leaf.shape) - len(ax))
        return NamedSharding(mesh, logical_to_spec(ax, shape=leaf.shape,
                                                   mesh=mesh, rules=rules))

    return jax.tree_util.tree_map_with_path(one, specs)


def tree_hint(tree, axes_tree):
    """:func:`shard_hint` over a whole pytree (inside jit): constrain every
    leaf to the spec its ``axes_tree`` annotation resolves to under the
    active mesh/rules.  Identity when no mesh is active.  Used to pin
    cache pytrees to a stable layout across decode steps."""
    mesh = active_mesh()
    if mesh is None or not isinstance(mesh, jax.sharding.Mesh):
        return tree

    def one(path, leaf):
        ax = _axes_at(axes_tree, path)
        if ax is None:
            spec = P()
        else:
            ax = list(ax)[:len(leaf.shape)]
            ax += [None] * (len(leaf.shape) - len(ax))
            spec = logical_to_spec(ax, shape=leaf.shape, mesh=mesh)
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(one, tree)
