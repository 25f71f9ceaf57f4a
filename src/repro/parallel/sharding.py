"""Logical-axis sharding rules (MaxText-style).

Every parameter is annotated with a tuple of *logical* axis names at init
time. A rule table per parallelism strategy maps logical names to mesh axes;
``resolve_spec`` applies the table with divisibility/size guards so the same
model code works on a 1-device CPU mesh, the 16x16 production pod and the
2x16x16 multi-pod mesh.

Strategies
----------
``tp``       params sharded over ``model`` only (Megatron TP); activations
             sharded over batch (``data``/``pod``) and heads/mlp (``model``).
``fsdp_tp``  additionally shards the ``embed`` logical axis over ``data``
             (and ``pod`` on a multi-pod mesh): the FSDP / ZeRO storage
             layout.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_POD = "pod"
AXIS_DATA = "data"
AXIS_MODEL = "model"

# logical axis -> mesh axis / tuple of mesh axes (None = replicated)
_TP_RULES: dict[str, object] = {
    "layers": None,        # stacked scan dim
    "stage": None,
    "embed": None,         # d_model
    "heads": "model",      # flattened q_dim / head dim products
    "kv_heads": "model",
    "mlp": "model",        # ffn hidden
    "vocab": "model",
    "experts": "model",    # expert parallelism over model axis
    "expert_mlp": None,
    "ssm_inner": "model",  # mamba d_inner / heads
    "ssm_state": None,
    "conv": None,
    "codebooks": None,
    "norm": None,
}

_FSDP_EXTRA: dict[str, object] = {
    # storage layout; on the multi-pod mesh the pod axis joins the shard
    "embed": ("pod", "data"),
}


def logical_rules(strategy: str) -> dict[str, str | None]:
    if strategy == "tp":
        return dict(_TP_RULES)
    if strategy == "fsdp_tp":
        rules = dict(_TP_RULES)
        rules.update(_FSDP_EXTRA)
        return rules
    raise ValueError(f"unknown strategy: {strategy}")


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes the batch dim is sharded over (pod folds into data)."""
    axes = tuple(a for a in (AXIS_POD, AXIS_DATA) if a in mesh.axis_names)
    return axes


def resolve_spec(
    axes: tuple[str | None, ...],
    shape: tuple[int, ...],
    mesh: Mesh,
    strategy: str = "tp",
) -> P:
    """Logical axes + concrete shape -> PartitionSpec with size guards.

    A mesh axis is dropped (replicated) when the dim is smaller than the axis
    size — GSPMD tolerates uneven sharding via padding, but sub-axis-size dims
    (e.g. 8 kv-heads over 16-way model axis) would waste >2x, so we replicate
    those instead.
    """
    rules = logical_rules(strategy)
    out: list[Any] = []
    used: set[str] = set()
    for dim, name in zip(shape, axes, strict=True):
        rule = rules.get(name) if name is not None else None
        cand = (rule,) if isinstance(rule, str) else (rule or ())
        mesh_axes = [a for a in cand
                     if a in mesh.axis_names and a not in used]
        # drop axes (outermost first) until the dim shards cleanly
        while mesh_axes:
            total = 1
            for a in mesh_axes:
                total *= mesh.shape[a]
            if dim >= total and dim % total == 0:
                break
            mesh_axes.pop(0)
        if not mesh_axes:
            out.append(None)
            continue
        used.update(mesh_axes)
        out.append(tuple(mesh_axes) if len(mesh_axes) > 1 else mesh_axes[0])
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def constrain(x, mesh: Mesh, *axes):
    """with_sharding_constraint by mesh axis names (None entries allowed)."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*axes)))
