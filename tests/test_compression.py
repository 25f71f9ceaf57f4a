"""int8 cross-pod gradient compression: quantizer properties + the wrapped
grad fn on a multi-'pod' host mesh (subprocess sets the device count)."""
from __future__ import annotations

import pathlib
import subprocess
import sys

import jax.numpy as jnp
import numpy as np

from tests.conftest import given, settings, st

from repro.parallel.compression import _quantize

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


@given(st.lists(st.floats(-1e3, 1e3, allow_nan=False, width=32),
                min_size=1, max_size=64))
@settings(max_examples=50, deadline=None)
def test_quantize_error_bound(vals):
    x = jnp.asarray(np.asarray(vals, np.float32))
    q, scale = _quantize(x)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(q, np.float32) * float(scale) - np.asarray(x))
    # symmetric RTN: error <= scale/2 (+ tiny eps slack)
    assert err.max() <= float(scale) / 2 + 1e-6


def test_quantize_zero_tensor():
    q, scale = _quantize(jnp.zeros((8,)))
    assert np.all(np.asarray(q) == 0)


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.parallel.compression import build_pod_compressed_grad_fn

mesh = jax.make_mesh((2, 2), ("pod", "data"))

def loss_fn(params, batch):
    pred = batch["x"] @ params["w"]
    l = jnp.mean((pred - batch["y"]) ** 2)
    return l, {"l": l}

grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
comp_fn = build_pod_compressed_grad_fn(grad_fn, mesh)
rng = np.random.default_rng(0)
params = {"w": jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)}
batch = {"x": jnp.asarray(rng.standard_normal((16, 8)), jnp.float32),
         "y": jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)}
with mesh:
    ps = jax.device_put(params, NamedSharding(mesh, P()))
    bs = jax.device_put(batch, NamedSharding(mesh, P("pod")))
    (l_c, m_c), g_c = jax.jit(comp_fn)(ps, bs)
    (l_r, m_r), g_r = jax.jit(grad_fn)(params, batch)
# loss identical (pmean of per-pod losses == global mean here)
np.testing.assert_allclose(float(l_c), float(l_r), rtol=1e-5)
# grads agree up to int8 quantization error
gc = np.asarray(g_c["w"]); gr = np.asarray(g_r["w"])
scale = np.abs(gr).max() / 127
assert np.abs(gc - gr).max() < 4 * scale + 1e-6, np.abs(gc - gr).max()
print("OK")
"""


def test_pod_compressed_grads_match_reference():
    r = subprocess.run([sys.executable, "-c", _SUBPROC],
                       capture_output=True, text=True,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            # the stripped env must keep jax on CPU: the
                            # host-device-count trick is CPU-only, and
                            # without the pin jax probes for TPU metadata
                            # for minutes before falling back
                            "JAX_PLATFORMS": "cpu"},
                       cwd=REPO_ROOT, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
