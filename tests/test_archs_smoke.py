"""Per-assigned-architecture smoke tests: reduced config, one train step +
prefill + decode on CPU, asserting output shapes and finiteness."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.configs.base import ParallelConfig
from repro.data.synthetic import synthetic_batches
from repro.models.lm import LM
from repro.train.train_step import build_train_step
from tests.conftest import SMOKE_PARALLEL, smoke_runconfig

ALL_ARCHS = sorted(ARCHS)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_full_config_matches_assignment(arch):
    cfg = get_config(arch)
    assert cfg.name == arch
    assert cfg.param_count() > 0
    # every full config must init abstractly (no allocation)
    params, axes = LM(cfg).init(None, abstract=True)
    assert jax.tree.all(jax.tree.map(
        lambda p: isinstance(p, jax.ShapeDtypeStruct), params))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step_smoke(arch):
    rcfg = smoke_runconfig(arch)
    lm = LM(rcfg.model)
    step_fn, rt, opt = build_train_step(lm, rcfg)
    params = lm.init(jax.random.key(0))[0]
    state = opt.init(params)
    batch = synthetic_batches(rcfg)(0)
    state, metrics = jax.jit(step_fn)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(state.step) == 1


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_decode_smoke(arch):
    cfg = dataclasses.replace(get_smoke_config(arch), n_patches=8)
    lm = LM(cfg)
    rt = lm.runtime(SMOKE_PARALLEL)
    params = lm.init(jax.random.key(0))[0]
    B, P, MAXLEN = 2, 16, 32
    tshape = (B, P) if cfg.n_codebooks <= 1 else (B, P, cfg.n_codebooks)
    batch = {"tokens": jnp.ones(tshape, jnp.int32)}
    if cfg.vision_stub:
        batch["patches"] = jnp.zeros((B, cfg.n_patches, cfg.d_model),
                                     jnp.dtype(cfg.dtype))
    logits, pre_caches, _ = lm.prefill(params, rt, batch)
    v = cfg.vocab_padded
    want = (B, v) if cfg.n_codebooks <= 1 else (B, cfg.n_codebooks, v)
    assert logits.shape == want
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    # splice into a max-capacity cache and take one decode step
    full = lm.init_cache(B, MAXLEN)
    caches = jax.tree.map(
        lambda d, s: jax.lax.dynamic_update_slice(
            d, s.astype(d.dtype), (0,) * d.ndim), full, pre_caches)
    plen = P + (cfg.n_patches if cfg.vision_stub else 0)
    lengths = jnp.full((B,), plen, jnp.int32)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    tok = tok[:, None] if cfg.n_codebooks <= 1 else tok[:, None, :]
    logits2, new_caches = lm.decode(params, rt, tok, lengths, caches)
    assert logits2.shape == want
    assert np.isfinite(np.asarray(logits2, np.float32)).all()
    # caches keep their structure/shapes
    jax.tree.map(lambda a, b: (a.shape, a.dtype) == (b.shape, b.dtype)
                 or pytest.fail("cache shape changed"), caches, new_caches)


def test_decode_matches_prefill_continuation():
    """Decoding token t+1 must equal prefilling t+1 tokens (same arch).
    f32 params: in bf16 the two paths differ only by accumulation order,
    which is not what this test is about."""
    cfg = dataclasses.replace(get_smoke_config("granite-3-8b"),
                              dtype="float32")
    lm = LM(cfg)
    rt = lm.runtime(SMOKE_PARALLEL)
    params = lm.init(jax.random.key(1))[0]
    toks = np.arange(1, 10)[None].astype(np.int32)  # (1, 9)
    lg_a, caches, _ = lm.prefill(params, rt, {"tokens": jnp.asarray(toks)})
    full = lm.init_cache(1, 16)
    caches = jax.tree.map(
        lambda d, s: jax.lax.dynamic_update_slice(
            d, s.astype(d.dtype), (0,) * d.ndim), full, caches)
    nxt = jnp.asarray([[10]], jnp.int32)
    lg_dec, _ = lm.decode(params, rt, nxt,
                          jnp.asarray([9], jnp.int32), caches)
    toks10 = np.concatenate([toks, [[10]]], axis=1)
    lg_b, _, _ = lm.prefill(params, rt, {"tokens": jnp.asarray(toks10)})
    np.testing.assert_allclose(np.asarray(lg_dec, np.float32),
                               np.asarray(lg_b, np.float32),
                               rtol=1e-4, atol=1e-4)
