"""AdamW with distributed state sharding (built from scratch — no optax).

Moments default to bf16 so a 1T-param model's optimizer state is 3x params
(bf16 p + m + v) instead of 12x. Update math runs in fp32 regardless of
storage dtype. The state is sharded however its arrays are placed.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    step: jax.Array
    params: Any
    m: Any
    v: Any


@dataclass(frozen=True)
class AdamW:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 1000
    min_lr_frac: float = 0.1
    moment_dtype: str = "bfloat16"

    def init(self, params) -> TrainState:
        mdt = jnp.dtype(self.moment_dtype)
        zeros = lambda p: jnp.zeros(p.shape, mdt)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            m=jax.tree.map(zeros, params),
            v=jax.tree.map(zeros, params),
        )

    def init_abstract(self, params) -> TrainState:
        mdt = jnp.dtype(self.moment_dtype)
        zeros = lambda p: jax.ShapeDtypeStruct(p.shape, mdt)
        return TrainState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            params=params,
            m=jax.tree.map(zeros, params),
            v=jax.tree.map(zeros, params),
        )

    def schedule(self, step):
        """Linear warmup then cosine decay to min_lr_frac."""
        step = step.astype(jnp.float32)
        warm = jnp.minimum(step / max(self.warmup_steps, 1), 1.0)
        t = jnp.clip((step - self.warmup_steps)
                     / max(self.total_steps - self.warmup_steps, 1), 0.0, 1.0)
        cos = 0.5 * (1 + jnp.cos(jnp.pi * t))
        frac = self.min_lr_frac + (1 - self.min_lr_frac) * cos
        return self.lr * warm * frac

    def apply(self, state: TrainState, grads) -> tuple[TrainState, dict]:
        # global-norm clip in fp32
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g in jax.tree.leaves(grads))
        gnorm = jnp.sqrt(sq)
        scale = jnp.minimum(1.0, self.grad_clip / jnp.maximum(gnorm, 1e-9))
        step = state.step + 1
        lr = self.schedule(step)
        b1, b2 = self.b1, self.b2
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            g = g.astype(jnp.float32) * scale
            mf = b1 * m.astype(jnp.float32) + (1 - b1) * g
            vf = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g)
            u = (mf / bc1) / (jnp.sqrt(vf / bc2) + self.eps)
            u = u + self.weight_decay * p.astype(jnp.float32)
            newp = p.astype(jnp.float32) - lr * u
            return newp.astype(p.dtype), mf.astype(m.dtype), vf.astype(v.dtype)

        flat_p, treedef = jax.tree.flatten(state.params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.m)
        flat_v = treedef.flatten_up_to(state.v)
        out = [upd(p, g, m, v) for p, g, m, v in
               zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = jax.tree.unflatten(treedef, [o[0] for o in out])
        new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
        new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
        return (TrainState(step, new_p, new_m, new_v),
                {"grad_norm": gnorm, "lr": lr})
