"""Minimal functional module substrate (no flax).

Params are nested dicts of arrays. Each init function receives a ``Scope``
and registers parameters with *logical axis* annotations; the scope builds
two parallel pytrees: ``params`` (arrays) and ``axes`` (tuples of logical
axis names, consumed by repro.parallel.sharding).

``abstract=True`` scopes produce ``jax.ShapeDtypeStruct`` leaves — parameter
shapes for trillion-param configs without allocating a single byte.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def _fold(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


class Scope:
    """Collects (params, axes) trees during init."""

    def __init__(self, key, dtype=jnp.bfloat16, abstract=False):
        self.key = key
        self.dtype = dtype
        self.abstract = abstract
        self.params: dict = {}
        self.axes: dict = {}

    def sub(self, name: str) -> "Scope":
        child = Scope(None if self.abstract else _fold(self.key, name),
                      self.dtype, self.abstract)
        self.params[name] = child.params
        self.axes[name] = child.axes
        return child

    def param(self, name, shape, axes, init="fan_in", scale=1.0, dtype=None):
        assert len(shape) == len(axes), (name, shape, axes)
        dtype = dtype or self.dtype
        if self.abstract:
            v = jax.ShapeDtypeStruct(tuple(shape), dtype)
        elif init == "zeros":
            v = jnp.zeros(shape, dtype)
        elif init == "ones":
            v = jnp.ones(shape, dtype)
        elif init == "fan_in":
            fan = shape[-2] if len(shape) >= 2 else shape[0]
            std = scale / (fan ** 0.5)
            v = (jax.random.normal(_fold(self.key, name), shape, jnp.float32)
                 * std).astype(dtype)
        elif init == "normal":
            v = (jax.random.normal(_fold(self.key, name), shape, jnp.float32)
                 * scale).astype(dtype)
        else:
            raise ValueError(init)
        self.params[name] = v
        self.axes[name] = tuple(axes)
        return v

    def done(self):
        return self.params, self.axes


def is_axes_leaf(x):
    return isinstance(x, tuple) and all(isinstance(y, (str, type(None))) for y in x)


def init_with_axes(init_fn, key, dtype=jnp.bfloat16, abstract=False):
    scope = Scope(key, dtype, abstract)
    init_fn(scope)
    return scope.done()


def stacked_init(init_fn, key, n: int, dtype=jnp.bfloat16, abstract=False,
                 stack_axis_name="layers"):
    """Stack ``n`` independent inits along a leading 'layers' axis."""
    if abstract:
        params, axes = init_with_axes(init_fn, None, dtype, abstract=True)
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), params)
    else:
        keys = jax.random.split(key, n)
        scope = Scope(keys[0], dtype)
        init_fn(scope)
        axes = scope.axes

        def one(k):
            s = Scope(k, dtype)
            init_fn(s)
            return s.params

        params = jax.vmap(one)(keys)
    axes = jax.tree.map(lambda a: (stack_axis_name,) + a, axes,
                        is_leaf=is_axes_leaf)
    return params, axes


def cast_tree(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype) if hasattr(x, "astype") else x,
                        tree)
