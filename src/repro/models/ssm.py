"""Mamba2 (state-space duality) blocks — chunked SSD prefill + recurrent decode.

The chunked dual form is TPU-native: within-chunk attention-like einsums hit
the MXU, the inter-chunk state pass is a short ``lax.scan``. The Pallas
``ssd_scan`` kernel mirrors the same blocking; this jnp path is its oracle and
the model-side implementation.

Sharding: d_inner (heads) over ``model``; B/C projections are group-shared
(MQA-like, ``ssm_groups``) and replicated.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import rmsnorm, rmsnorm_init


def mamba_init(scope, cfg):
    d, di, ds, nh, ng = (cfg.d_model, cfg.d_inner, cfg.d_state,
                         cfg.n_ssm_heads, cfg.ssm_groups)
    scope.param("w_z", (d, di), ("embed", "ssm_inner"))
    scope.param("w_x", (d, di), ("embed", "ssm_inner"))
    scope.param("w_B", (d, ng * ds), ("embed", "ssm_state"))
    scope.param("w_C", (d, ng * ds), ("embed", "ssm_state"))
    scope.param("w_dt", (d, nh), ("embed", "ssm_inner"))
    scope.param("conv_x", (cfg.conv_dim, di), ("conv", "ssm_inner"))
    scope.param("conv_B", (cfg.conv_dim, ng * ds), ("conv", "ssm_state"))
    scope.param("conv_C", (cfg.conv_dim, ng * ds), ("conv", "ssm_state"))
    scope.param("a_log", (nh,), ("ssm_inner",), init="normal", scale=0.5,
                dtype=jnp.float32)
    scope.param("d_skip", (nh,), ("ssm_inner",), init="ones", dtype=jnp.float32)
    scope.param("dt_bias", (nh,), ("ssm_inner",), init="zeros", dtype=jnp.float32)
    rmsnorm_init(scope, "norm", di)
    scope.param("w_out", (di, d), ("ssm_inner", "embed"))


def causal_conv(x, w, prev=None):
    """Depthwise causal conv. x: (B,S,ch), w: (k,ch). prev: (B,k-1,ch) or None."""
    k = w.shape[0]
    if prev is None:
        prev = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prev, x], axis=1)
    out = jax.lax.conv_general_dilated(
        xp, w[:, None, :].astype(x.dtype),
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NHC", "HIO", "NHC"),
        feature_group_count=x.shape[2])
    return jax.nn.silu(out.astype(jnp.float32)).astype(x.dtype), xp[:, -(k - 1):]


def _expand_groups(t, nh):
    """(B,...,ng,ds) -> (B,...,nh,ds) by repeating groups."""
    ng = t.shape[-2]
    if ng == nh:
        return t
    rep = nh // ng
    return jnp.repeat(t, rep, axis=-2)


def ssd_chunked(xh, dt, A, Bg, Cg, chunk, state0=None):
    """Chunked SSD. xh: (B,S,nh,hp); dt: (B,S,nh) f32; A: (nh,) f32;
    Bg/Cg: (B,S,ng,ds). Returns (y (B,S,nh,hp), final_state (B,nh,hp,ds))."""
    B, S, nh, hp = xh.shape
    ds = Bg.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    f32 = jnp.float32
    Bh = _expand_groups(Bg, nh).astype(f32).reshape(B, nc, chunk, nh, ds)
    Ch = _expand_groups(Cg, nh).astype(f32).reshape(B, nc, chunk, nh, ds)
    xc = xh.astype(f32).reshape(B, nc, chunk, nh, hp)
    dtc = dt.reshape(B, nc, chunk, nh)
    if state0 is None:
        state0 = jnp.zeros((B, nh, hp, ds), f32)

    def step(state, inp):
        xb, dtb, Bb, Cb = inp  # (B,Q,nh,hp), (B,Q,nh), (B,Q,nh,ds) x2
        dA = dtb * A  # (B,Q,nh) (<= 0)
        cs = jnp.cumsum(dA, axis=1)
        # intra-chunk (dual / attention-like) term
        L = jnp.exp(cs[:, :, None, :] - cs[:, None, :, :])  # (B,Q,Q,nh)
        idx = jnp.arange(xb.shape[1])
        L = jnp.where((idx[:, None] >= idx[None, :])[None, :, :, None], L, 0.0)
        scores = jnp.einsum("bihs,bjhs->bijh", Cb, Bb) * L
        xdt = xb * dtb[..., None]
        y = jnp.einsum("bijh,bjhp->bihp", scores, xdt)
        # inter-chunk (recurrent) term
        y = y + jnp.einsum("bihs,bhps->bihp", Cb, state) * jnp.exp(cs)[..., None]
        decay_out = jnp.exp(cs[:, -1:, :] - cs)  # (B,Q,nh)
        new_state = state * jnp.exp(cs[:, -1])[:, :, None, None] + jnp.einsum(
            "bjhs,bjhp->bhps", Bb * decay_out[..., None], xdt)
        return new_state, y

    xs = (xc.transpose(1, 0, 2, 3, 4), dtc.transpose(1, 0, 2, 3),
          Bh.transpose(1, 0, 2, 3, 4), Ch.transpose(1, 0, 2, 3, 4))
    final_state, ys = jax.lax.scan(step, state0, xs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, S, nh, hp)
    return y.astype(xh.dtype), final_state


def ssd_decode_step(xh, dt, A, Bg, Cg, state):
    """One token. xh: (B,nh,hp); dt: (B,nh); Bg/Cg: (B,ng,ds);
    state: (B,nh,hp,ds) -> (y (B,nh,hp), new_state)."""
    nh = xh.shape[1]
    f32 = jnp.float32
    Bh = _expand_groups(Bg, nh).astype(f32)
    Ch = _expand_groups(Cg, nh).astype(f32)
    dA = jnp.exp(dt * A)  # (B,nh)
    xdt = xh.astype(f32) * dt[..., None]
    new_state = state * dA[..., None, None] + jnp.einsum("bhs,bhp->bhps", Bh, xdt)
    y = jnp.einsum("bhs,bhps->bhp", Ch, new_state)
    return y.astype(xh.dtype), new_state


def mamba_apply(p, cfg, x, *, conv_state=None, ssm_state=None, decode=False):
    """x: (B,S,d) (S==1 token slice when decode) -> (y, (conv_state, ssm_state)).

    conv_state: dict of (B,k-1,ch) buffers; ssm_state: (B,nh,hp,ds).
    """
    B = x.shape[0]
    nh, hp, ds, ng = (cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.d_state,
                      cfg.ssm_groups)
    z = jnp.einsum("bsd,de->bse", x, p["w_z"])
    xs = jnp.einsum("bsd,de->bse", x, p["w_x"])
    Bm = jnp.einsum("bsd,de->bse", x, p["w_B"])
    Cm = jnp.einsum("bsd,de->bse", x, p["w_C"])
    dt = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                    p["w_dt"].astype(jnp.float32))
    cs = conv_state or {}
    xs, cx = causal_conv(xs, p["conv_x"], cs.get("x"))
    Bm, cb = causal_conv(Bm, p["conv_B"], cs.get("B"))
    Cm, cc = causal_conv(Cm, p["conv_C"], cs.get("C"))
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (B,S,nh)
    A = -jnp.exp(p["a_log"])
    S = x.shape[1]
    xh = xs.reshape(B, S, nh, hp)
    Bg = Bm.reshape(B, S, ng, ds)
    Cg = Cm.reshape(B, S, ng, ds)
    if decode:
        y, new_state = ssd_decode_step(xh[:, 0], dt[:, 0], A, Bg[:, 0], Cg[:, 0],
                                       ssm_state)
        y = y[:, None]
    else:
        y, new_state = ssd_chunked(xh, dt, A, Bg, Cg, cfg.ssm_chunk, ssm_state)
    y = y + (xh.astype(jnp.float32) * p["d_skip"][:, None]).astype(y.dtype)
    y = y.reshape(B, S, cfg.d_inner)
    y = rmsnorm(p["norm"], y, cfg.norm_eps)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"])
    return out, ({"x": cx, "B": cb, "C": cc}, new_state)
