"""Train-step builder: value_and_grad + microbatch accumulation.

``build_train_step`` returns (step_fn, runtime, optimizer). Gradient
accumulation scans over microbatches with bf16 accumulators, deferring the
cross-``data`` reduction to the weight update — the accumulation itself
adds no collectives.

Cross-pod gradient compression (int8 + error feedback) is available for the
multi-pod mesh via ``ParallelConfig.grad_compress_pod``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig
from repro.models.lm import LM
from repro.train.optimizer import AdamW, TrainState


def make_optimizer(rcfg: RunConfig) -> AdamW:
    return AdamW(
        lr=rcfg.learning_rate, b1=rcfg.adam_b1, b2=rcfg.adam_b2,
        eps=rcfg.adam_eps, weight_decay=rcfg.weight_decay,
        grad_clip=rcfg.grad_clip, warmup_steps=rcfg.warmup_steps,
        total_steps=rcfg.total_steps, moment_dtype=rcfg.moment_dtype)


def build_train_step(lm: LM, rcfg: RunConfig, mesh=None):
    """Returns (train_step, rt, opt). train_step(state, batch)->(state, metrics)."""
    rt = lm.runtime(rcfg.parallel, mesh)
    opt = make_optimizer(rcfg)
    n_micro = rcfg.parallel.microbatches

    def loss_fn(params, batch):
        return lm.loss(params, rt, batch)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    if rcfg.parallel.grad_compress_pod and mesh is not None:
        from repro.parallel.compression import build_pod_compressed_grad_fn
        grad_fn = build_pod_compressed_grad_fn(grad_fn, mesh)

    def train_step(state: TrainState, batch):
        if n_micro <= 1:
            (loss, metrics), grads = grad_fn(state.params, batch)
        else:
            def split(x):
                return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
            micro = jax.tree.map(split, batch)

            def acc_step(carry, mb):
                g_acc, l_acc = carry
                (loss, metrics), g = grad_fn(state.params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), g_acc, g)
                return (g_acc, l_acc + loss), metrics

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.bfloat16), state.params)
            (grads, loss), metrics_all = jax.lax.scan(
                acc_step, (g0, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda g: (g / n_micro).astype(jnp.float32),
                                 grads)
            loss = loss / n_micro
            metrics = jax.tree.map(lambda m: m[-1], metrics_all)
        state, opt_metrics = opt.apply(state, grads)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return state, metrics

    return train_step, rt, opt
