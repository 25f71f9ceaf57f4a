"""Chunked causal attention: gradients against a plain softmax, and no
gradient through the running max."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import NEG_INF, chunked_attention

B, S, H, HD = 2, 64, 4, 32


def _naive(q, k, v):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / HD ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, NEG_INF)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _inputs():
    keys = jax.random.split(jax.random.key(0), 4)
    return [jax.random.normal(k, (B, S, H, HD), jnp.float32) for k in keys]


def _grad(fn, q, k, v, cot):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * cot), argnums=(0, 1, 2))(
        q, k, v)


@pytest.mark.parametrize("impl", ["masked", "triangular"])
@pytest.mark.parametrize("q_chunk,kv_chunk", [(16, 16), (16, 32), (64, 64)])
def test_chunked_attention_grad_matches_softmax(impl, q_chunk, kv_chunk):
    q, k, v, cot = _inputs()
    want = _grad(_naive, q, k, v, cot)
    got = _grad(lambda *a: chunked_attention(
        *a, q_chunk=q_chunk, kv_chunk=kv_chunk, impl=impl), q, k, v, cot)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["masked", "triangular"])
def test_chunked_attention_grad_skips_the_max(impl):
    # differentiating a max compares every score with it (``eq``) and
    # divides by the count of ties; when the compiler recomputes the scores
    # at another precision that count can be 0, and the gradient NaN
    q, k, v, cot = _inputs()
    chunk = 16
    jaxpr = jax.make_jaxpr(lambda *a: _grad(lambda *b: chunked_attention(
        *b, q_chunk=chunk, kv_chunk=chunk, impl=impl), *a, cot))(q, k, v)

    def eq_shapes(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "eq":
                yield from (tuple(x.aval.shape) for x in eqn.invars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eq_shapes(sub)

    assert (B, H, chunk, chunk) not in set(eq_shapes(jaxpr.jaxpr))
