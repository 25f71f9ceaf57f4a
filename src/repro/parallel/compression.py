"""Cross-pod int8 gradient compression (beyond-paper distributed-opt trick).

Multi-pod meshes reduce gradients twice: within a pod over the fast ICI
(``data`` axis, handled by GSPMD), and across pods over the slow inter-pod
links (``pod`` axis). We make the *pod* reduction explicit with a
partial-manual ``shard_map`` (``axis_names={"pod"}``; ``data``/``model``
stay GSPMD-auto) and exchange int8-quantized tensors via
``collective_permute`` — 4x fewer inter-pod bytes than an fp32 all-reduce.

Quantization is per-tensor symmetric round-to-nearest. For 2 pods the
dequantize-then-add formulation avoids int8 saturation entirely; >2 pods
fall back to an int32 psum of int8 payloads (XLA still moves int8-scale
bytes only after its own narrowing pass — documented in EXPERIMENTS.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import AXIS_POD


def _quantize(x):
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _pod_sum_compressed(x, n_pods: int):
    q, scale = _quantize(x)
    if n_pods == 2:
        perm = [(0, 1), (1, 0)]
        q_other = jax.lax.ppermute(q, AXIS_POD, perm)
        s_other = jax.lax.ppermute(scale, AXIS_POD, perm)
        out = q.astype(jnp.float32) * scale + q_other.astype(jnp.float32) * s_other
    else:
        # generic: psum the int8 payload widened to int32; scales pmax'd
        s = jax.lax.pmax(scale, AXIS_POD)
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
        out = jax.lax.psum(q.astype(jnp.int32), AXIS_POD).astype(jnp.float32) * s
    return (out / n_pods).astype(x.dtype)  # mean over pods


def build_pod_compressed_grad_fn(grad_fn, mesh):
    """Wrap a value_and_grad fn so the pod-axis reduction is int8-compressed.

    grad_fn(params, batch) -> ((loss, metrics), grads). Params must be
    pod-replicated (they are: placement only uses data/model axes); batch is
    sharded over pod on dim 0.
    """
    if mesh is None or AXIS_POD not in mesh.axis_names or mesh.shape[AXIS_POD] == 1:
        return grad_fn
    n_pods = mesh.shape[AXIS_POD]

    def wrapped(params, batch):
        def body(params, batch):
            (loss, metrics), grads = grad_fn(params, batch)
            grads = jax.tree.map(lambda g: _pod_sum_compressed(g, n_pods), grads)
            loss = jax.lax.pmean(loss, AXIS_POD)
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, AXIS_POD), metrics)
            return (loss, metrics), grads

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(AXIS_POD)),   # prefix specs: pod placement only
            out_specs=P(),
            axis_names={AXIS_POD},
            check_vma=False,
        )(params, batch)

    return wrapped
