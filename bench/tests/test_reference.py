"""The reference's convolutions, written out tap by tap, agree with XLA's
own convolution at small sizes on the CPU, in value and in gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import reference as R

HIGHEST = lax.Precision.HIGHEST
CASES = [  # rank, spatial, kernel, stride, pads (conv) / crop (deconv)
    (2, (5, 6), 3, 2, (1, 1)),
    (2, (4, 4), 3, 2, (0, 1)),
    (3, (4, 3, 5), 3, 1, (1, 1)),
    (3, (3, 4, 2), 3, 2, (0, 1)),
    (3, (4, 4, 4), 1, 1, (0, 0)),
]


def _dims(rank):
    sp = "HWD"[:rank]
    return (f"N{sp}C", f"{sp}IO", f"N{sp}C")


def _lax_conv(x, w, stride, pads):
    return lax.conv_general_dilated(x, w, stride, pads,
                                    dimension_numbers=_dims(x.ndim - 2),
                                    precision=HIGHEST)


def _lax_deconv(x, w, stride, crop):
    rank = x.ndim - 2
    k = w.shape[:rank]
    pads = [(kk - 1 - lo, kk - 1 - hi) for kk, (lo, hi) in zip(k, crop)]
    return lax.conv_general_dilated(
        x, jnp.flip(w, tuple(range(rank))), (1,) * rank, pads,
        lhs_dilation=stride, dimension_numbers=_dims(rank), precision=HIGHEST)


@pytest.mark.parametrize("op", ["conv", "deconv"])
@pytest.mark.parametrize("rank, sp, k, s, pad", CASES)
def test_tapwise_matches_lax(op, rank, sp, k, s, pad):
    kx, kw = jax.random.split(jax.random.PRNGKey(rank * 10 + k + s))
    x = jax.random.normal(kx, (2, *sp, 3))
    w = jax.random.normal(kw, (*(k,) * rank, 3, 4))
    stride, pads = (s,) * rank, (pad,) * rank
    ours = getattr(R, op)
    theirs = _lax_conv if op == "conv" else _lax_deconv
    np.testing.assert_allclose(ours(x, w, stride, pads),
                               theirs(x, w, stride, pads), rtol=1e-5,
                               atol=1e-5)
    g1 = jax.grad(lambda x, w: jnp.sum(ours(x, w, stride, pads) ** 2),
                  (0, 1))(x, w)
    g2 = jax.grad(lambda x, w: jnp.sum(theirs(x, w, stride, pads) ** 2),
                  (0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
