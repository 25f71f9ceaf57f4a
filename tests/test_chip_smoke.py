"""chip_smoke.py's phases on small shapes on the CPU: the checks it makes on
the chip (token counts, logits against the contiguous engine and a float32
reference, the fleet against its emulated twin, exact resume, planted
faults, disjoint devices, loss gaps) run here on every test run.

Each case runs in a child process with four host CPU devices, so that the
four-device elastic phase has a real mesh and no test shares JAX state.
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys

import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]

_CHILD = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.getcwd())
import jax
import chip_smoke as cs
from repro.configs.base import smoke_reduce
from repro.models.lm import LM

phase = sys.argv[1]
label = "cpu"
if phase == "serve":
    lm = LM(smoke_reduce(cs.granite(2)))
    params = jax.jit(lambda k: lm.init(k)[0])(jax.random.key(cs.SEED))
    cs.serve_phases(lm, lm.runtime(), params, label, max_batch=8, max_len=64,
                    page_size=8, n_requests=6, prompt_lens=(8, 16, 24),
                    new_tokens=(8, 16), workflows=1)
else:
    rcfg = cs.train_config(smoke_reduce(cs.granite(2)), seq_len=32, batch=8)
    if phase == "train":
        cs.train_phase(jax.devices()[0], rcfg, label)
    else:
        cs.elastic_phase(jax.devices(), rcfg, label)
print("PHASE OK")
"""


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True,
        cwd=REPO_ROOT, timeout=600,
        # a stripped env pinned to the CPU: without the pin jax probes for
        # TPU metadata for minutes before falling back
        env={"PYTHONPATH": "src", "PATH": os.environ.get("PATH", ""),
             "JAX_PLATFORMS": "cpu", **env})


@pytest.mark.parametrize("phase", ["serve", "train", "elastic"])
def test_chip_smoke_phase_passes_on_cpu_shapes(phase):
    r = _run(["-c", _CHILD, phase])
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    assert r.stdout.rstrip().endswith("PHASE OK")


def test_chip_smoke_refuses_to_run_without_a_tpu():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr
