"""Jit'd public wrapper for the Pallas IOM deconv kernel.

Handles: rank lifting to canonical 3D (the large, tileable dim leading),
channel padding to block multiples, the phase-major weight gather (each
phase's valid taps contiguous, feeding the kernel's tap-batched matmuls),
leading-dim zero-padding to the planner's tile grid,
border cropping — symmetric or per-dim ``(lo, hi)`` pairs, the
``UniformLayer.padding`` convention — and a custom VJP that runs BOTH
cotangents on the same uniform Pallas grid as the forward (deconv's
adjoint is a strided convolution — the engine's first-class forward conv,
see ``repro.kernels.conv``): ``dx`` is a stride-S gather-convolution of
``dy`` and ``dw`` a set of per-tap [bci, bco] contractions reduced across
the sequential grid dims — training steps never leave the paper's engine.

Since PR 4 every call runs against a ``repro.core.engine.UniformEngine``:
the engine's ``EngineConfig`` carries what used to be per-call tuning
kwargs (blocks, VMEM budget, interpret, output dtype) and its
geometry-keyed cache means the unified planner
(``repro.core.tiling.plan_uniform_tiles``) runs once per layer geometry,
not once per op invocation.  The fused 4D grid with in-kernel halo
overlap-add (see ``kernel.py``) still serves any input size as ONE
``pallas_call``.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as _engine
from repro.core.functional import _canon, canon_padding, deconv_output_shape
from repro.kernels import common as _common
from repro.kernels.deconv import kernel as _k

# host-side canonicalisation shared with kernels.conv.ops
_pad_axis_to = _common.pad_axis_to
_lift_3d = _common.lift_3d


def _phase_major(w3, kernel3, stride3, dilation3=None):
    """[K..., ci, co] -> [prod(K), ci, co] in phase-major tap order.

    Alias of ``kernels.common.phase_major_weights`` — each phase's valid
    taps land contiguously, so the kernel bodies slice a whole phase for
    their tap-batched matmul.
    """
    return _common.phase_major_weights(w3, kernel3, stride3, dilation3)


def _core_call(x3, w3, stride3, kernel3, block_ci, block_co, interpret,
               dtile=None, n_dtiles=1, out_dtype=None,
               dilation3=None, groups=1,
               scale=None, bias=None, activation="none", alpha=0.2):
    """Pad channels/weights/leading dim and invoke the fused kernel ONCE.

    The leading dim is zero-padded to ``n_dtiles * dtile`` — always at least
    ``M_d - 1`` rows beyond the data, which the kernel's halo contract
    requires.  Output is cropped back to Eq. (1) extent.  ``w3`` is
    ``[*K, Ci/G, Co]``: the contracted dim is already per-group, the
    produced dim (and x's channels, the per-cout dequant ``scale``, and the
    bias) pad PER GROUP so the kernel's group-blocked channel grid stays
    aligned.
    """
    ci, co = x3.shape[-1], w3.shape[-1]
    cog = co // groups
    dilation3 = tuple(dilation3) if dilation3 is not None else (1, 1, 1)
    out3 = deconv_output_shape(x3.shape[1:4], kernel3, stride3, 0,
                               dilation3)
    x3 = _common.pad_group_axis(x3, -1, groups, block_ci)
    w3 = _common.pad_group_axis(_pad_axis_to(w3, -2, block_ci), -1,
                                groups, block_co)
    m_max = _common.phase_geometry(kernel3, stride3, dilation3)
    w3 = _phase_major(w3, kernel3, stride3, dilation3)
    if scale is not None:
        scale = _common.pad_group_axis(
            jnp.broadcast_to(scale, (co,)).reshape(-1), 0, groups, block_co)
    if bias is not None:
        bias = _common.pad_group_axis(bias.reshape(-1), 0, groups, block_co)
    if dtile is None:
        dtile = x3.shape[1] + m_max[0] - 1
        n_dtiles = 1
    d_pad = n_dtiles * dtile
    assert d_pad >= x3.shape[1] + m_max[0] - 1, (d_pad, x3.shape, m_max)
    x3 = jnp.pad(x3, [(0, 0), (0, d_pad - x3.shape[1])]
                 + [(0, 0)] * 3)
    y = _k.deconv_pallas_3d(x3, w3, kernel=kernel3, stride=stride3,
                            block_ci=min(block_ci, x3.shape[-1]),
                            block_co=min(block_co, w3.shape[-1]),
                            dtile=dtile, dilation=dilation3, groups=groups,
                            scale=scale, bias=bias,
                            activation=activation, alpha=alpha,
                            interpret=interpret,
                            out_dtype=out_dtype)
    return _common.crop_group_axis(y[:, :out3[0]], -1, groups, cog)


def _deconv_fwd_impl(x, w, b, w_scale, stride, padding, dilation, groups,
                     activation, alpha, engine):
    cfg = engine.config
    interpret = cfg.pallas_interpret
    rank = x.ndim - 2
    stride_r = _canon(stride, rank)
    pads_r = canon_padding(padding, rank)
    dil_r = _common.canon_dilation(dilation, rank)
    x3, w3, stride3, squeeze = _lift_3d(x, w, stride_r)
    kernel3 = w3.shape[:3]
    dilation3 = _common.lift_tuple3(dil_r, rank)
    in_sp3 = x3.shape[1:4]

    plan = engine.plan("deconv", in_sp3, kernel3, stride3,
                       x3.shape[-1], w3.shape[-1], groups=groups,
                       dilation=dilation3,
                       in_dtype_bytes=_common.operand_plan_bytes(x3.dtype),
                       w_dtype_bytes=_common.operand_plan_bytes(w3.dtype))
    y3 = _core_call(x3, w3, stride3, kernel3, plan.block_ci, plan.block_co,
                    interpret, dtile=plan.dtile, n_dtiles=plan.n_dtiles,
                    out_dtype=cfg.preferred_element_type,
                    dilation3=dilation3, groups=groups,
                    scale=w_scale, bias=b,
                    activation=activation, alpha=alpha)

    # un-lift and crop ((lo, hi) per dim — asymmetric crops supported);
    # the fused epilogue commutes with the border crop (elementwise)
    y = jnp.squeeze(y3, axis=squeeze) if squeeze else y3
    if any(lo or hi for lo, hi in pads_r):
        idx = (slice(None),) + tuple(
            slice(lo, dim - hi)
            for (lo, hi), dim in zip(pads_r, y.shape[1:-1])
        ) + (slice(None),)
        y = y[idx]
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _deconv(x, w, b, w_scale, stride, padding, dilation, groups, activation,
            alpha, engine):
    return _deconv_fwd_impl(x, w, b, w_scale, stride, padding, dilation,
                            groups, activation, alpha, engine)


def _fwd(x, w, b, w_scale, stride, padding, dilation, groups, activation,
         alpha, engine):
    y = _deconv(x, w, b, w_scale, stride, padding, dilation, groups,
                activation, alpha, engine)
    # the activation gradient is recoverable from the OUTPUT for every
    # supported activation, so y is the only extra residual — and only
    # when an activation is actually fused
    return y, (x, w, b, w_scale, y if activation != "none" else None)


def _bwd_einsum(stride, padding, res, dy):
    """The pre-Pallas backward, kept VERBATIM as the benchmark baseline: a
    Python loop of K^d full-array f32 einsums with no tiling, no VMEM
    planning, and an unconditional upcast.  Production gradients go through
    ``_bwd`` below — the uniform Pallas grid."""
    x, w = res
    rank = x.ndim - 2
    stride_r = _canon(stride, rank)
    pads_r = canon_padding(padding, rank)
    kernel_r = w.shape[:rank]
    in_sp = x.shape[1:-1]

    # un-crop dy back to the full Eq.(1) extent
    if any(lo or hi for lo, hi in pads_r):
        dy = jnp.pad(dy, [(0, 0)] + list(pads_r) + [(0, 0)])
    dy = dy.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)

    # dx[n,i,ci] = sum_k dy[n, i*S+k, co] w[k,ci,co]
    # dw[k,ci,co] = sum_{n,i} x[n,i,ci] dy[n, i*S+k, co]
    dx = jnp.zeros_like(xf)
    dw = jnp.zeros_like(wf)
    for k in itertools.product(*(range(kk) for kk in kernel_r)):
        sl = (slice(None),) + tuple(
            slice(kj, kj + sj * ij, sj)
            for kj, sj, ij in zip(k, stride_r, in_sp)) + (slice(None),)
        dy_k = dy[sl]                                     # [N, *I, Co]
        dx = dx + jnp.einsum("n...o,io->n...i", dy_k, wf[k])
        dw = dw.at[k].set(jnp.einsum("n...i,n...o->io", xf, dy_k))
    return dx.astype(x.dtype), dw.astype(w.dtype)


def _bwd(stride, padding, dilation, groups, activation, alpha, engine,
         res, dy):
    """Training backward on the uniform Pallas grid.

    Deconv's adjoint is a strided convolution, so both cotangents reuse the
    forward's fused 4D grid (see ``kernel.py``): ``dx`` is a stride-S
    gather-convolution of ``dy`` against the tap weights (phases collapsed
    to one, reversed d-tile iteration), ``dw`` a per-tap [bci, bco]
    contraction accumulated across the sequential grid dims in VMEM.  One
    cached ``engine.plan(..., backward=True)`` decision budgets the working
    sets of both kernels; inputs stay in their storage dtype (accumulation
    is f32 in-kernel — no full-array HBM upcast).

    A fused epilogue peels off first: the activation gradient is computed
    from the saved OUTPUT (relu -> y>0, leaky -> slope by sign, tanh ->
    1-y^2), and the bias cotangent is the pre-activation cotangent summed
    over every non-channel axis.  Grouped layers reshuffle the weight
    layout so each adjoint contracts only within its own group slab.

    Quantized-weight forwards stay f32-exact here: the backward runs on
    the DEQUANTIZED weights ``w * w_scale`` (the per-cout scale commutes
    with the adjoint contractions), so dx/db match the float op applied to
    the dequantized weights bit-for-bit.  The int8 weights themselves get
    a float0 cotangent; the scale's cotangent folds the dequantized-weight
    gradient back per channel.
    """
    x, w, b, w_scale, y = res
    if jnp.issubdtype(x.dtype, jnp.integer):
        raise NotImplementedError(
            "backward through quantized activations is not supported; "
            "train with Precision(act_quant='none')")
    if w_scale is not None:
        wq, w = w, (w.astype(jnp.float32) * w_scale).astype(jnp.float32)
    interpret = engine.config.pallas_interpret
    rank = x.ndim - 2
    stride_r = _canon(stride, rank)
    pads_r = canon_padding(padding, rank)
    dil_r = _common.canon_dilation(dilation, rank)

    if activation != "none":
        dy = dy * _common.activation_grad_from_output(y, activation, alpha)
    db = (dy.sum(axis=tuple(range(dy.ndim - 1))).astype(b.dtype)
          if b is not None else None)

    # un-crop dy back to the full Eq.(1) extent
    if any(lo or hi for lo, hi in pads_r):
        dy = jnp.pad(dy, [(0, 0)] + list(pads_r) + [(0, 0)])

    x3, w3, stride3, squeeze = _lift_3d(x, w, stride_r)
    dy3 = jnp.expand_dims(dy, squeeze) if squeeze else dy
    kernel3 = w3.shape[:3]
    dilation3 = _common.lift_tuple3(dil_r, rank)
    ci, co = x3.shape[-1], w3.shape[-1]
    cig, cog = ci // groups, co // groups

    plan = engine.plan("deconv", x3.shape[1:4], kernel3, stride3, ci, co,
                       groups=groups, dilation=dilation3, backward=True)

    # pad channels to the blocks (per group, so group slabs stay aligned)
    # and leading dims to the tile grid: x to n_dtiles*dtile rows, dy to
    # the matching output extent (the kernels' alignment contract; zero
    # rows pair only with zeros)
    x3p = _common.pad_group_axis(x3, -1, groups, plan.block_ci)
    dy3p = _common.pad_group_axis(dy3, -1, groups, plan.block_co)
    d_pad = plan.n_dtiles * plan.dtile
    x3p = jnp.pad(x3p, [(0, 0), (0, d_pad - x3.shape[1])] + [(0, 0)] * 3)
    dy3p = jnp.pad(dy3p, [(0, 0), (0, d_pad * stride3[0] - dy3.shape[1])]
                   + [(0, 0)] * 3)

    # dx contracts Co within each group and produces ALL Ci: regroup the
    # padded weight [*K, Ci/G, Co] -> [*K, G*Ci/G, Co/G] so the conv-side
    # kernel's group-blocked maps pick the right slab
    w3p = _common.pad_group_axis(_pad_axis_to(w3, -2, plan.block_ci), -1,
                                 groups, plan.block_co)
    cig_p, cog_p = w3p.shape[-2], w3p.shape[-1] // groups
    w3dx = w3p.reshape(*kernel3, cig_p, groups, cog_p)
    w3dx = jnp.moveaxis(w3dx, -2, -3).reshape(*kernel3, groups * cig_p,
                                              cog_p)

    dx3 = _k.deconv_dx_pallas_3d(
        dy3p, _phase_major(w3dx, kernel3, stride3, dilation3),
        kernel=kernel3, stride=stride3, block_ci=plan.block_ci,
        block_co=plan.block_co, dtile=plan.dtile, dilation=dilation3,
        groups=groups, interpret=interpret,
        out_dtype=x.dtype)[:, :x3.shape[1]]
    dx3 = _common.crop_group_axis(dx3, -1, groups, cig)
    dw3 = _k.deconv_dw_pallas_3d(
        x3p, dy3p, kernel=kernel3, stride=stride3, block_ci=plan.block_ci,
        block_co=plan.block_co, dtile=plan.dtile, dilation=dilation3,
        groups=groups, interpret=interpret,
        out_dtype=w.dtype)[:, :cig]
    dw3 = _common.crop_group_axis(dw3, -1, groups, cog)
    # the kernel emits taps phase-major; invert back to kernel-element order
    dw3 = dw3[jnp.asarray(_common.phase_major_inverse(kernel3, stride3,
                                                      dilation3))]

    dx = jnp.squeeze(dx3, axis=squeeze) if squeeze else dx3
    dw = dw3.reshape(w.shape)
    if w_scale is None:
        return dx, dw, db, None
    # dw above is the gradient of the DEQUANTIZED weight.  Chain back:
    # d(scale) folds it against the stored quantized values per channel,
    # and integer weights take the required float0 cotangent.
    full = wq.astype(jnp.float32) * dw
    if jnp.shape(w_scale) == ():
        dscale = full.sum()
    else:
        dscale = full.sum(axis=tuple(range(full.ndim - 1))).reshape(
            jnp.shape(w_scale))
    dscale = dscale.astype(w_scale.dtype)
    if jnp.issubdtype(wq.dtype, jnp.integer):
        dwq = np.zeros(wq.shape, dtype=jax.dtypes.float0)
    else:
        dwq = (dw * w_scale).astype(wq.dtype)
    return dx, dwq, db, dscale


_deconv.defvjp(_fwd, _bwd)


def deconv(x: jax.Array, w: jax.Array, stride, padding=0, *,
           dilation=1, groups: int = 1, bias: jax.Array | None = None,
           w_scale: jax.Array | None = None,
           activation: str = "none", alpha: float = 0.2,
           block_ci: int | None = None, block_co: int | None = None,
           interpret: bool | None = None,
           max_tile_bytes: int | None = None,
           preferred_element_type=None,
           engine=None) -> jax.Array:
    """Public op: uniform 1D/2D/3D IOM deconvolution via the Pallas kernel.

    x: [N, *spatial, Cin]; w: [*K, Cin/groups, Cout]; returns channels-last
    output of extent (I-1)*S + (K-1)*dilation + 1 - lo - hi per dim.
    ``padding`` is a scalar, per-dim scalars, or per-dim ``(lo, hi)`` pairs
    (the ``UniformLayer.padding`` convention — ``((0, 1),) * rank`` crops
    to exact doubling).  ``groups`` blocks channels lax-style
    (``feature_group_count``; ``groups == Cin`` is depthwise) and
    ``bias``/``activation`` fuse the layer epilogue into the kernel's
    accumulator flush — no separate elementwise pass is traced.
    ``w_scale`` (per-cout, shape ``(Cout,)`` or scalar) marks ``w`` as
    scaled — typically int8 from ``repro.quant.quantize_weights`` — and
    fuses the dequant multiply into that same epilogue, scale → bias →
    activation, on the f32 accumulator.

    The tuning keywords are compatibility sugar: they resolve to a memoized
    ``repro.core.engine.default_engine`` whose ``EngineConfig`` carries
    them, so repeated calls share one plan cache.  Passing ``engine=``
    directly (what ``UniformEngine.deconv`` does) is the configured path —
    mixing it with per-call knobs is an error.
    """
    if engine is None:
        engine = _engine.default_engine(
            method="pallas", block_ci=block_ci, block_co=block_co,
            interpret=interpret, max_tile_bytes=max_tile_bytes,
            preferred_element_type=preferred_element_type)
    elif any(v is not None for v in (block_ci, block_co, interpret,
                                     max_tile_bytes, preferred_element_type)):
        raise ValueError("per-call tuning kwargs and an explicit engine are "
                         "mutually exclusive; set them on the EngineConfig")
    if activation not in _common.ACTIVATIONS:
        raise ValueError(f"activation must be one of {_common.ACTIVATIONS}, "
                         f"got {activation!r}")
    rank = x.ndim - 2
    if x.shape[-1] % groups or w.shape[-1] % groups:
        raise ValueError(f"groups={groups} must divide Cin={x.shape[-1]} "
                         f"and Cout={w.shape[-1]}")
    return _deconv(x, w, bias, w_scale, _canon(stride, rank),
                   canon_padding(padding, rank),
                   _common.canon_dilation(dilation, rank), groups,
                   activation, float(alpha), engine)
