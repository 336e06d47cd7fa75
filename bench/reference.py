"""Plain float32 reference layers: ``lax`` and ``jnp`` only.

Nothing here imports the program.  Convolutions are written out from their
definitions as one matrix product per kernel tap, so that they share no
lowering with the program's kernels or with XLA's convolution emitter.
Every contraction runs at ``precision=HIGHEST`` (full f32 on a TPU).
"""

from __future__ import annotations

import itertools

import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _taps(kernel):
    return itertools.product(*(range(k) for k in kernel))


def _contract(x, w_tap):
    """x [..., cin] times one tap's w [cin, cout], at full f32."""
    return jnp.einsum("...i,io->...o", x, w_tap, precision=HIGHEST)


def conv(x, w, stride, padding):
    """Strided convolution, as its definition: y[o] = sum over taps t of
    x[o * s + t - lo] w[t], with ``padding`` per-dim (lo, hi) zero pads.
    One matrix product per tap over a strided slice of the padded input."""
    rank = x.ndim - 2
    kernel = w.shape[:rank]
    xp = jnp.pad(x, ((0, 0), *padding, (0, 0)))
    out = [(xp.shape[1 + d] - kernel[d]) // stride[d] + 1
           for d in range(rank)]
    y = 0.0
    for t in _taps(kernel):
        start = (0, *t, 0)
        limit = (xp.shape[0], *((o - 1) * s + tt + 1 for o, s, tt
                                in zip(out, stride, t)), xp.shape[-1])
        xs = lax.slice(xp, start, limit, (1, *stride, 1))
        y = y + _contract(xs, w[t])
    return y


def deconv(x, w, stride, crop):
    """Transposed convolution, as its definition: y[i * s + t] += x[i] w[t]
    over the full extent ``(S - 1) * s + K`` per dim, then cropped by
    (lo, hi).  One matrix product per tap, placed on the output's stride
    grid by interior zero padding."""
    rank = x.ndim - 2
    kernel = w.shape[:rank]
    sp = x.shape[1:1 + rank]
    full = [(n - 1) * s + k for n, s, k in zip(sp, stride, kernel)]
    y = 0.0
    for t in _taps(kernel):
        pads = [(0, 0, 0)] + [
            (tt, fl - tt - (n - 1) * s - 1, s - 1)
            for tt, fl, n, s in zip(t, full, sp, stride)] + [(0, 0, 0)]
        y = y + lax.pad(_contract(x, w[t]), 0.0, pads)
    sl = tuple(slice(lo, fl - hi) for (lo, hi), fl in zip(crop, full))
    return y[(slice(None), *sl, slice(None))]


def activation(h, kind: str):
    if kind == "relu":
        return jnp.maximum(h, 0.0)
    if kind == "tanh":
        return jnp.tanh(h)
    if kind == "none":
        return h
    raise ValueError(f"unknown activation {kind!r}")
