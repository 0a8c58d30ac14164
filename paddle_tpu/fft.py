"""paddle.fft — discrete Fourier transform surface.

Parity: python/paddle/fft.py (36 functions: c2c/r2c/c2r 1-D/2-D/n-D
transforms + helpers, norm modes 'forward'|'backward'|'ortho';
kernels paddle/phi/kernels/*/fft_*). TPU design: jnp.fft → XLA FFT HLO
(differentiable; batched over leading dims).
"""

from __future__ import annotations

import jax.numpy as jnp

from .core.tensor import Tensor
from .ops.dispatch import apply_op, ensure_tensor

__all__ = [
    "fft", "ifft", "rfft", "irfft", "hfft", "ihfft",
    "fft2", "ifft2", "rfft2", "irfft2", "hfft2", "ihfft2",
    "fftn", "ifftn", "rfftn", "irfftn", "hfftn", "ihfftn",
    "fftfreq", "rfftfreq", "fftshift", "ifftshift",
]

_NORMS = {"forward", "backward", "ortho", None}


def _norm(norm):
    if norm not in _NORMS:
        raise ValueError(
            f"Unexpected norm: {norm!r}. Norm should be forward, backward or ortho")
    return norm or "backward"


def _wrap1(name, jfn, complex_in=False):
    def op(x, n=None, axis=-1, norm="backward", name=None):
        t = ensure_tensor(x)
        nm = _norm(norm)
        return apply_op(op.__name__,
                        lambda a: jfn(a, n=n, axis=axis, norm=nm), t)

    op.__name__ = name
    return op


def _wrap2(name, jfn):
    def op(x, s=None, axes=(-2, -1), norm="backward", name=None):
        t = ensure_tensor(x)
        nm = _norm(norm)
        return apply_op(op.__name__,
                        lambda a: jfn(a, s=s, axes=tuple(axes), norm=nm), t)

    op.__name__ = name
    return op


def _wrapn(name, jfn):
    def op(x, s=None, axes=None, norm="backward", name=None):
        t = ensure_tensor(x)
        nm = _norm(norm)
        ax = tuple(axes) if axes is not None else None
        return apply_op(op.__name__,
                        lambda a: jfn(a, s=s, axes=ax, norm=nm), t)

    op.__name__ = name
    return op


fft = _wrap1("fft", jnp.fft.fft)
ifft = _wrap1("ifft", jnp.fft.ifft)
rfft = _wrap1("rfft", jnp.fft.rfft)
irfft = _wrap1("irfft", jnp.fft.irfft)
hfft = _wrap1("hfft", jnp.fft.hfft)
ihfft = _wrap1("ihfft", jnp.fft.ihfft)

fft2 = _wrap2("fft2", jnp.fft.fft2)
ifft2 = _wrap2("ifft2", jnp.fft.ifft2)
rfft2 = _wrap2("rfft2", jnp.fft.rfft2)
irfft2 = _wrap2("irfft2", jnp.fft.irfft2)


def hfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return hfftn(x, s=s, axes=axes, norm=norm)


def ihfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return ihfftn(x, s=s, axes=axes, norm=norm)


fftn = _wrapn("fftn", jnp.fft.fftn)
ifftn = _wrapn("ifftn", jnp.fft.ifftn)
rfftn = _wrapn("rfftn", jnp.fft.rfftn)
irfftn = _wrapn("irfftn", jnp.fft.irfftn)


def hfftn(x, s=None, axes=None, norm="backward", name=None):
    """n-D Hermitian FFT: c2c forward over inner axes, c2r (hfft) over the
    last axis — the reference's fft_c2c + fft_c2r composition."""
    t = ensure_tensor(x)
    nm = _norm(norm)

    def f(a):
        ax = tuple(axes) if axes is not None else tuple(range(a.ndim))
        for i, axi in enumerate(ax[:-1]):
            a = jnp.fft.fft(a, n=None if s is None else s[i], axis=axi, norm=nm)
        return jnp.fft.hfft(a, n=None if s is None else s[-1], axis=ax[-1], norm=nm)

    return apply_op("hfftn", f, t)


def ihfftn(x, s=None, axes=None, norm="backward", name=None):
    """Inverse of hfftn: r2c (ihfft) over the last axis, then c2c inverse
    over the inner axes."""
    t = ensure_tensor(x)
    nm = _norm(norm)

    def f(a):
        ax = tuple(axes) if axes is not None else tuple(range(a.ndim))
        a = jnp.fft.ihfft(a, n=None if s is None else s[-1], axis=ax[-1], norm=nm)
        for i, axi in enumerate(ax[:-1]):
            a = jnp.fft.ifft(a, n=None if s is None else s[i], axis=axi, norm=nm)
        return a

    return apply_op("ihfftn", f, t)


def fftfreq(n, d=1.0, dtype=None, name=None) -> Tensor:
    return Tensor(jnp.fft.fftfreq(n, d=d).astype(dtype or "float32"))


def rfftfreq(n, d=1.0, dtype=None, name=None) -> Tensor:
    return Tensor(jnp.fft.rfftfreq(n, d=d).astype(dtype or "float32"))


def fftshift(x, axes=None, name=None) -> Tensor:
    return apply_op("fftshift", lambda a: jnp.fft.fftshift(a, axes=axes),
                    ensure_tensor(x))


def ifftshift(x, axes=None, name=None) -> Tensor:
    return apply_op("ifftshift", lambda a: jnp.fft.ifftshift(a, axes=axes),
                    ensure_tensor(x))
