"""The controls: the reference computed one step of precision below
what a configuration states, the step that would tempt a later PR.
For a model served in bfloat16, int8: every linear layer's weights
rounded to int8 by output channel and its activations by token (W8A8).
For bfloat16 training, fp8 (e4m3, per-tensor scale) matmul inputs. A
check's limit has to fail these."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _int8(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def int8_matmul(x, w):
    """A matmul of int8 values: ``x [tokens, in]`` rounded by the
    absolute maximum of each token, ``w [in, out]`` by that of each
    output channel; the products add up in float32."""
    return jnp.matmul(_int8(x, -1), _int8(w, 0))


def _fp8(x):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(x.dtype) / scale
    # straight through: the backward pass sees the rounded values, and
    # its cotangents are not rounded (unscaled they would underflow)
    return x + jax.lax.stop_gradient(q - x)


def fp8_matmul(x, w):
    """A matmul whose two inputs were rounded to fp8 e4m3 under a
    per-tensor scale (straight-through gradient)."""
    return jnp.matmul(_fp8(x), _fp8(w))
