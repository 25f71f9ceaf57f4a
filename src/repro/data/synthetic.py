"""Deterministic synthetic token pipeline.

``synthetic_batches`` materializes seeded concrete training batches for
smoke tests and end-to-end examples (with the MusicGen delay pattern
applied to codebook streams, and stub patch embeddings for the VLM).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RunConfig


def _token_shape(cfg: ModelConfig, B: int, S: int) -> tuple:
    if cfg.n_codebooks > 1:
        return (B, S, cfg.n_codebooks)
    return (B, S)


def apply_delay_pattern(tokens: np.ndarray, pad: int = 0) -> np.ndarray:
    """MusicGen delay pattern: codebook c shifted right by c steps."""
    B, S, C = tokens.shape
    out = np.full_like(tokens, pad)
    for c in range(C):
        out[:, c:, c] = tokens[:, : S - c, c]
    return out


def synthetic_batches(rcfg: RunConfig, mesh=None):
    """Returns batch_fn(step)->batch of concrete arrays (seeded, CPU-sized)."""
    cfg = rcfg.model
    shape = rcfg.shape

    def batch_fn(step: int):
        rng = np.random.default_rng(rcfg.seed * 100003 + step)
        B, S = shape.global_batch, shape.seq_len
        S_txt = S - cfg.n_patches if cfg.vision_stub else S
        # learnable structure: each row is an arithmetic token sequence
        # (stride 1..4, random phase) so CE demonstrably decreases.
        tshape = _token_shape(cfg, B, S_txt + 1)
        phase = rng.integers(0, cfg.vocab_size, (B,) + (1,) * (len(tshape) - 1))
        stride = rng.integers(1, 5, (B,) + (1,) * (len(tshape) - 1))
        t = np.arange(S_txt + 1).reshape(1, S_txt + 1,
                                         *([1] * (len(tshape) - 2)))
        toks = ((phase + stride * t) % cfg.vocab_size).astype(np.int32)
        toks = np.broadcast_to(toks, tshape).copy()
        if cfg.n_codebooks > 1:
            toks = apply_delay_pattern(toks)
        batch = {
            "tokens": jnp.asarray(toks[:, :-1]),
            "targets": jnp.asarray(toks[:, 1:]),
            "mask": jnp.ones((B, S_txt), jnp.float32),
        }
        if cfg.vision_stub:
            patches = rng.standard_normal((B, cfg.n_patches, cfg.d_model),
                                          dtype=np.float32)
            batch["patches"] = jnp.asarray(patches, jnp.dtype(cfg.dtype))
        return batch

    return batch_fn
