"""Ring attention (sequence-parallel prefill) vs the attention oracle."""
from __future__ import annotations

import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.collectives import ring_attention
from repro.kernels.ref import flash_attention_ref

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_single_device_fallback_matches_oracle():
    rng = np.random.default_rng(0)
    B, S, H, KVH, hd = 2, 64, 8, 2, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KVH, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KVH, hd)), jnp.float32)
    out = ring_attention(q, k, v, mesh=None)
    rep = jnp.repeat(k, H // KVH, axis=2), jnp.repeat(v, H // KVH, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = rep[0].transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = rep[1].transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    ref = flash_attention_ref(qf, kf, vf, causal=True)
    ref = ref.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.collectives import ring_attention
from repro.kernels.ref import flash_attention_ref

mesh = jax.make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(1)
B, S, H, KVH, hd = 4, 64, 8, 4, 32   # GQA: kv rotates unrepeated
q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
k = jnp.asarray(rng.standard_normal((B, S, KVH, hd)), jnp.float32)
v = jnp.asarray(rng.standard_normal((B, S, KVH, hd)), jnp.float32)
with mesh:
    out = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh))(q, k, v)
kr = jnp.repeat(k, H // KVH, axis=2)
vr = jnp.repeat(v, H // KVH, axis=2)
qf = q.transpose(0,2,1,3).reshape(B*H, S, hd)
kf = kr.transpose(0,2,1,3).reshape(B*H, S, hd)
vf = vr.transpose(0,2,1,3).reshape(B*H, S, hd)
ref = flash_attention_ref(qf, kf, vf, causal=True)
ref = ref.reshape(B,H,S,hd).transpose(0,2,1,3)
err = float(jnp.max(jnp.abs(out - ref)))
assert err < 1e-5, err
print("OK", err)
"""


def test_ring_matches_oracle_on_sharded_mesh():
    r = subprocess.run([sys.executable, "-c", _SUBPROC],
                       capture_output=True, text=True,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            # keep jax on CPU in the stripped env: the
                            # host-device-count trick is CPU-only, and
                            # without the pin jax probes for TPU metadata
                            # for minutes before falling back
                            "JAX_PLATFORMS": "cpu"},
                       cwd=REPO_ROOT, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
