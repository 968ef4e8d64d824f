"""Logical-axis sharding rules (DP/FSDP/TP/EP/SP) — MaxText-style.

Models annotate tensors with *logical* axis names; this module maps them to
mesh axes for whatever mesh is active.  Parameters carry a parallel tree of
logical-name tuples built at init time; :func:`params_pspecs` turns that into
``PartitionSpec``s (adding ZeRO/FSDP sharding of large replicated dims over
the data axis), and :func:`shard` applies activation constraints in-graph.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "AxisRules", "DEFAULT_RULES", "SERVE_TP_RULES", "use_mesh",
    "current_mesh", "logical_spec", "shard", "params_pspecs",
    "named_sharding", "FSDP_THRESHOLD", "Axes", "A",
]


@dataclasses.dataclass(frozen=True)
class Axes:
    """Logical dim names for one parameter — an opaque pytree LEAF, so a tree
    of ``Axes`` mirrors the params tree structurally."""

    names: tuple

    def __iter__(self):
        return iter(self.names)


def A(*names: str | None) -> Axes:
    return Axes(tuple(names))

# logical axis -> preferred mesh axes (first available wins)
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),       # DP: batch over pod x data
    "seq": (),                      # activations: sequence replicated
    "kv_seq": ("model",),           # SP: sequence-sharded decode KV caches
    "embed": (),                    # d_model replicated
    "heads": ("model",),            # TP: attention heads
    "kv_heads": ("model",),
    "ff": ("model",),               # TP: FFN hidden
    "vocab": ("model",),            # TP: embedding/logits vocab dim
    "experts": ("model",),          # EP: MoE expert dim
    "moe_ff": ("data",),            # EP: expert hidden dim (resident 2D)
    "expert_cap": (),
    "fsdp": ("data",),              # ZeRO/FSDP axis for large weights
    "state": (),                    # recurrent state dims
    "ctl": ("data",),               # controller batches (jax_controller)
}

FSDP_THRESHOLD = 2**20  # params larger than 1M elements get FSDP sharding

# Serving tensor-parallel rule overlay: ONLY the attention-head family (and
# MoE experts) shards over the model axis.  Training's default rules also
# split ff/vocab, which changes matmul contraction order (psum of partials)
# and therefore bits; the serve engine's contract is token-identity with
# single-device, so everything except head-parallel attention + EP MoE stays
# replicated and the per-head math is bit-for-bit the single-device program.
SERVE_TP_RULES: dict[str, tuple[str, ...]] = {
    "batch": (),
    "kv_seq": (),
    "ff": (),
    "vocab": (),
    "moe_ff": (),
    "fsdp": (),
    "ctl": (),
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Mesh | None = None
        self.rules: dict[str, tuple[str, ...]] = dict(DEFAULT_RULES)
        self.fsdp: bool = True


_CTX = _Ctx()
AxisRules = dict


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: dict | None = None, fsdp: bool = True):
    """Activate a mesh + logical rules for model tracing."""
    prev = (_CTX.mesh, _CTX.rules, _CTX.fsdp)
    _CTX.mesh = mesh
    _CTX.rules = dict(DEFAULT_RULES, **(rules or {}))
    _CTX.fsdp = fsdp
    try:
        with mesh:
            yield mesh
    finally:
        _CTX.mesh, _CTX.rules, _CTX.fsdp = prev


def current_mesh() -> Mesh | None:
    return _CTX.mesh


def _resolve(name: str, taken: set[str], dim_size: int | None = None
             ) -> tuple[str, ...]:
    """Mesh axes for one logical name (skipping axes not in the mesh, axes
    already used by another dim of the same tensor, and — when ``dim_size``
    is known — axes that would not divide the dimension evenly)."""
    mesh = _CTX.mesh
    if mesh is None:
        return ()
    axes = []
    prod = 1
    for ax in _CTX.rules.get(name, ()):
        if ax in mesh.axis_names and ax not in taken:
            if dim_size is not None and dim_size % (prod * mesh.shape[ax]):
                continue
            prod *= mesh.shape[ax]
            axes.append(ax)
            taken.add(ax)
    return tuple(axes)


def logical_spec(*names: str | None) -> P:
    """PartitionSpec for a tensor annotated with logical dim names."""
    taken: set[str] = set()
    parts = []
    for n in names:
        if n is None:
            parts.append(None)
            continue
        axes = _resolve(n, taken)
        parts.append(axes if len(axes) > 1 else (axes[0] if axes else None))
    return P(*parts)


def shard(x: jax.Array, *names: str | None) -> jax.Array:
    """Apply a logical sharding constraint (no-op without an active mesh).
    Axes that do not divide the concrete dim evenly are dropped."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    taken: set[str] = set()
    parts = []
    for i, n in enumerate(names):
        if n is None:
            parts.append(None)
            continue
        axes = _resolve(n, taken, x.shape[i])
        parts.append(axes if len(axes) > 1 else (axes[0] if axes else None))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))


def named_sharding(*names: str | None) -> NamedSharding:
    mesh = _CTX.mesh
    assert mesh is not None, "named_sharding requires an active mesh"
    return NamedSharding(mesh, logical_spec(*names))


def _leaf_pspec(axes_names: tuple, shape: tuple[int, ...]) -> P:
    """Logical names -> PartitionSpec for one parameter, with FSDP: shard the
    largest still-replicated dim over the data axis for big params."""
    taken: set[str] = set()
    parts: list = []
    for i, n in enumerate(axes_names):
        if n is None:
            parts.append(None)
        else:
            axes = _resolve(n, taken, shape[i])
            parts.append(axes if len(axes) > 1 else (axes[0] if axes else None))
    mesh = _CTX.mesh
    residual_only = _CTX.fsdp == "residual"
    if (mesh is not None and _CTX.fsdp and "data" not in taken
            and "data" in mesh.axis_names
            and not (residual_only and taken)):
        size = 1
        for s in shape:
            size *= s
        if size >= FSDP_THRESHOLD:
            data_size = mesh.shape["data"]
            # biggest unsharded, divisible dim gets the fsdp axis
            cands = [i for i, p in enumerate(parts)
                     if p is None and shape[i] % data_size == 0]
            if cands:
                i = max(cands, key=lambda j: shape[j])
                parts[i] = "data"
    return P(*parts)


def params_pspecs(params, logical_tree):
    """Map a params pytree + parallel tree of :class:`Axes` to PartitionSpecs."""
    return jax.tree.map(
        lambda p, ax: _leaf_pspec(tuple(ax.names), p.shape),
        params, logical_tree,
    )
