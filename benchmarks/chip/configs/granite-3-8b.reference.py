"""Plain reference of granite-3-8b, as the configuration files beside it state it.

A dense decoder with grouped-query attention: token embedding; per layer
RMSNorm, Q/K/V projections, rotary embedding (the half-split rotation of
the Hugging Face ``rotate_half``), causal softmax attention with each KV
head shared by ``H / KVH`` query heads, the output projection and a
residual add; RMSNorm, a SwiGLU MLP (``silu(x W_gate) * (x W_up)``, then
``W_down``) and a residual add; a final RMSNorm and the output head,
tied to the embedding where the configuration says so. Granite's four scalar multipliers are
applied where the published model applies them: the embedding times
``embedding_multiplier``, attention scores times ``attention_multiplier``
(in place of 1/sqrt(head_dim)), each residual branch times
``residual_multiplier``, and the logits divided by ``logits_scaling``.

Everything here is straightforward ``jax.numpy`` in float32 with
``default_matmul_precision("highest")``: no kernel, no cache, no batching
trick. It imports nothing of the system under test and takes nothing the
system made: the benchmark makes the weights from the run's seed with
``init``, hands the system a copy in the system's layout, and makes them
again from the seed for the reference once the system's state is freed.

``quant="fp8"`` is the control: every input of every projection is
rounded to float8 e4m3 (weights per output column, activations per
token, each scaled to the format's range), the next precision below the
bfloat16 the configuration serves in. It must fail the check the system
passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F8_MAX = 448.0      # largest finite float8_e4m3fn
#: how much wider than 1/fan_in the residual branches' output projections
#: are drawn (``init``)
BRANCH_GAIN = 16.0


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "L": cfg["num_hidden_layers"], "H": h,
            "KVH": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def init(cfg: dict, key) -> dict:
    """Seeded random weights in the served dtype: matrices N(0, 1/fan_in),
    the embedding N(0, initializer_range^2), norm scales 1, with two
    departures that make the output depend on the context, as trained
    weights make it. W_q and W_k are drawn wider, so that the attention
    scores spread by about 1 under the published attention multiplier (at
    1/fan_in the multiplier would leave attention all but uniform). W_o and
    W_down are drawn ``BRANCH_GAIN`` times wider, so that the residual
    branches outweigh the scaled embedding: under the head tied to the
    embedding, the embedding's own row would otherwise give the current
    token the top logit at nearly every position, whatever the cache held.
    Layers are stacked on a leading axis of length L."""
    s = sizes(cfg)
    d, L, hd = s["d"], s["L"], s["hd"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    # a score is m * q.k with q, k ~ N(0, d std^2) per component
    qk_std = (cfg["attention_multiplier"] * hd ** 0.5 * d) ** -0.5

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def layer(k):
        ks = jax.random.split(k, 7)
        return {
            "norm1": jnp.ones((d,), dtype),
            "wq": normal(ks[0], (d, s["H"] * hd), qk_std),
            "wk": normal(ks[1], (d, s["KVH"] * hd), qk_std),
            "wv": normal(ks[2], (d, s["KVH"] * hd), d ** -0.5),
            "wo": normal(ks[3], (s["H"] * hd, d),
                         BRANCH_GAIN * (s["H"] * hd) ** -0.5),
            "norm2": jnp.ones((d,), dtype),
            "w_gate": normal(ks[4], (d, s["ff"]), d ** -0.5),
            "w_up": normal(ks[5], (d, s["ff"]), d ** -0.5),
            "w_down": normal(ks[6], (s["ff"], d),
                             BRANCH_GAIN * s["ff"] ** -0.5),
        }

    k_embed, k_head, k_layers = jax.random.split(key, 3)
    w = {
        "embed": normal(k_embed, (s["V"], d), cfg["initializer_range"]),
        "final_norm": jnp.ones((d,), dtype),
        "layers": jax.vmap(layer)(jax.random.split(k_layers, L)),
    }
    if not cfg["tie_word_embeddings"]:
        w["head"] = normal(k_head, (d, s["V"]), d ** -0.5)
    return w


def _q8(x, axis):
    """Round to float8 e4m3, scaled per slice along ``axis``'s complement."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _matmul(a, w, quant):
    if quant == "fp8":
        a, w = _q8(a, -1), _q8(w, 0)
    return a @ w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (B, S, heads, hd) at positions 0..S-1."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, x, lw, quant):
    s = sizes(cfg)
    B, S, _ = x.shape
    H, KVH, hd = s["H"], s["KVH"], s["hd"]
    eps = cfg["rms_norm_eps"]
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    h = _rms(x, lw["norm1"], eps)
    q = _rope(_matmul(h, lw["wq"], quant).reshape(B, S, H, hd),
              cfg["rope_theta"])
    k = _rope(_matmul(h, lw["wk"], quant).reshape(B, S, KVH, hd),
              cfg["rope_theta"])
    v = _matmul(h, lw["wv"], quant).reshape(B, S, KVH, hd)
    k = jnp.repeat(k, H // KVH, axis=2)
    v = jnp.repeat(v, H // KVH, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H * hd)
    x = x + cfg["residual_multiplier"] * _matmul(o, lw["wo"], quant)
    h = _rms(x, lw["norm2"], eps)
    mlp = (jax.nn.silu(_matmul(h, lw["w_gate"], quant))
           * _matmul(h, lw["w_up"], quant))
    return x + cfg["residual_multiplier"] * _matmul(mlp, lw["w_down"], quant)


def forward(cfg: dict, w: dict, tokens, quant: str | None = None):
    """Logits (B, S, V) in float32 at every position of ``tokens`` (B, S)."""
    with jax.default_matmul_precision("highest"):
        x = w["embed"].astype(jnp.float32)[tokens]
        x = x * cfg["embedding_multiplier"]

        def body(x, lw):
            return _layer(cfg, x, lw, quant), None

        x, _ = jax.lax.scan(body, x, w["layers"])
        x = _rms(x, w["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
        head = w["embed"].T if cfg["tie_word_embeddings"] else w["head"]
        logits = _matmul(x, head.astype(jnp.float32), quant)
        return logits / cfg["logits_scaling"]


def loss(cfg: dict, w: dict, tokens, targets, quant: str | None = None):
    """Mean next-token cross-entropy over every position, in float32."""
    logits = forward(cfg, w, tokens, quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)
