"""Logical-axis sharding rules.

Models annotate tensors with *logical* axis names; this module resolves them
to mesh axes present in the current abstract mesh. Rules are swappable via
``rules_override`` — the primary hillclimbing lever for the §Perf loop.

Logical axes:
    batch    activation batch dim            -> ("pod","data")
    fsdp     weight d_model (ZeRO/FSDP) dim  -> ("pod","data")
    tensor   heads / mlp / vocab TP dim      -> ("model",)
    kv_seq   sharded KV-cache sequence dim   -> ("model",)   [decode]
    kv_seq_long  long-context KV sequence    -> ("data","model") [long_500k]
    expert   MoE expert dim                  -> ()  (replicated axis; ff uses tensor)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P


DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "fsdp": ("pod", "data"),
    "tensor": ("model",),
    "kv_seq": ("model",),
    "kv_seq_long": ("data", "model"),
    "expert": (),
}

_local = threading.local()


def _rules() -> dict[str, tuple[str, ...]]:
    return getattr(_local, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def rules_override(**kw):
    """Temporarily replace logical->mesh rules (hillclimbing)."""
    old = _rules()
    new = dict(old)
    for k, v in kw.items():
        new[k] = tuple(v) if v else ()
    _local.rules = new
    try:
        yield
    finally:
        _local.rules = old


def mesh_axis_names() -> tuple[str, ...]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return ()
    return tuple(mesh.axis_names)


def resolve(logical: Optional[str]) -> Optional[tuple[str, ...]]:
    """Resolve one logical name to mesh axes present in the current mesh."""
    if logical is None:
        return None
    present = set(mesh_axis_names())
    axes = tuple(a for a in _rules().get(logical, ()) if a in present)
    return axes or None

def ax(*logicals: Optional[str]) -> P:
    """Build a PartitionSpec from logical names (None = replicated dim)."""
    out = []
    for name in logicals:
        r = resolve(name)
        if r is None:
            out.append(None)
        elif len(r) == 1:
            out.append(r[0])
        else:
            out.append(r)
    return P(*out)


def constrain(x, *logicals: Optional[str]):
    """with_sharding_constraint using logical names; no-op without a mesh."""
    if not mesh_axis_names():
        return x
    return jax.lax.with_sharding_constraint(x, ax(*logicals))


def weight_gather(cfg, w, axes):
    """Constrain a weight gathered over its fsdp dims (tensor dims kept)
    when cfg.gather_weights — steers XLA to all-gather-weights instead of
    partial-matmul + huge activation all-reduces on token-heavy steps."""
    if not getattr(cfg, "gather_weights", False) or not mesh_axis_names():
        return w
    return jax.lax.with_sharding_constraint(
        w, ax(*[a if a == "tensor" else None for a in axes]))


def axis_size(logical: str) -> int:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    n = 1
    for a in _rules().get(logical, ()):
        n *= sizes.get(a, 1)
    return n
