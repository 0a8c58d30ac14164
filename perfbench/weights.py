"""Seeded weights made on the device.

Every element of every leaf is a pure function of (seed, leaf index,
element index): a 32-bit integer hash feeds a Box-Muller transform. So
a whole model is filled on the device in the type it is served in, a
leaf can be made again later without keeping a copy (the training
check regenerates the initial weights to measure how far they moved),
and the reference makes the same values itself instead of taking the
program's arrays. Integer hashing is exact on every backend; the
transcendentals are the backend's own, so values agree within one
process and device, which is all a run compares.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_M32 = 0xFFFFFFFF


def _mix_py(x):
    x &= _M32
    x ^= x >> 16
    x = (x * 0x7FEB352D) & _M32
    x ^= x >> 15
    x = (x * 0x846CA68B) & _M32
    x ^= x >> 16
    return x


def _mix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def salts(seed, n_leaves):
    """One uint32 salt per leaf. ``seed`` is any non-negative integer
    (the driver's exceed 2**31)."""
    seed = int(seed)
    base = _mix_py(seed & _M32) ^ _mix_py((seed >> 32) + 0x9E3779B9)
    return np.array([_mix_py(base + 0x85EBCA77 * (i + 1))
                     for i in range(n_leaves)], np.uint32)


def leaf(shape, salt, mean, std, dtype):
    """Normal(mean, std) values of ``shape``; ``salt`` is a traced
    uint32 so that a new seed does not compile a new program."""
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for axis in range(len(shape) - 1, -1, -1):
        idx = idx + jax.lax.broadcasted_iota(
            jnp.uint32, shape, axis) * jnp.uint32(stride)
        stride *= shape[axis]
    h1 = _mix(idx ^ salt)
    h2 = _mix(h1 + jnp.uint32(0x9E3779B9))
    u1 = ((h1 >> 8).astype(jnp.float32) + 1.0) * (1.0 / (1 << 24))
    u2 = (h2 >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    z = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos((2.0 * math.pi) * u2)
    return (mean + std * z).astype(dtype)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "upcast"))
def _leaf(salt, mean, std, shape, dtype, upcast):
    out = leaf(shape, salt, mean, std, dtype)
    return out if upcast is None else out.astype(upcast)


def make(spec, seed, dtype, upcast=None):
    """All leaves of ``spec`` (name -> (shape, mean, std), ordered) on
    the default device, each made by the one small program of its shape
    (a model has a handful of shapes, so a cold run compiles in a second
    and a warm one loads next to nothing). ``upcast`` widens the values
    after they were rounded to ``dtype``: the reference computes in
    float32 on the weights that are served."""
    s = salts(seed, len(spec))
    dtype = jnp.dtype(dtype)
    upcast = None if upcast is None else jnp.dtype(upcast)
    return {k: _leaf(jnp.uint32(s[i]), jnp.float32(mean), jnp.float32(std),
                     shape=tuple(shape), dtype=dtype, upcast=upcast)
            for i, (k, (shape, mean, std)) in enumerate(spec.items())}
