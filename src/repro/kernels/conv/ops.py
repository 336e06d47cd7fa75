"""Jit'd public wrapper for the first-class strided-conv Pallas kernel.

Handles: rank lifting to canonical 3D (the large, tileable dim leading),
host-side ``(lo, hi)`` padding, channel padding to block multiples, the
channel-swapped phase-major weight gather (the conv kernel contracts Cin,
so weights go in as ``[prod(K), Cout, Cin]``), leading-dim alignment to the
planner's tile grid, and a custom VJP that CLOSES THE ADJOINT LOOP on the
uniform engine:

  * the forward is ``conv_pallas_3d`` — the deconv grid's dx body promoted
    out of its backward-only role (see ``kernels/conv/kernel.py``);
  * dx of a conv IS a deconv, so the dx cotangent reuses the deconv forward
    kernel (``deconv_pallas_3d`` via ``kernels.deconv.ops._core_call``)
    with the channel roles swapped;
  * dw reuses ``deconv_dw_pallas_3d`` with the (x, dy) roles swapped —
    conv's stride-1-indexed array is dy where deconv's was x.

Since PR 4 every call runs against a ``repro.core.engine.UniformEngine``:
one cached ``engine.plan("conv", ...)`` decision (the shared VMEM model of
``repro.core.tiling.plan_uniform_tiles``) budgets all three
``pallas_call``s of a training step, exactly as the deconv op does — and
the geometry-keyed cache plans each layer shape once, not per invocation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as _engine
from repro.core.engine import conv_output_shape
from repro.core.functional import _canon, canon_padding
from repro.kernels import common as _common
from repro.kernels.conv import kernel as _ck
from repro.kernels.deconv import kernel as _dk
from repro.kernels.deconv import ops as _dops


def _lift_padding(pads, rank):
    """Lift per-dim (lo, hi) pairs onto the canonical 3D layout."""
    if rank == 3:
        return tuple(pads)
    if rank == 2:
        return (pads[0], (0, 0), pads[1])
    return ((0, 0), (0, 0), pads[0])


def _window(arr, pads3, sizes3):
    """Slice ``arr[:, lo : lo + size, ..., :]`` per dim, zero-padding any
    tail the source does not cover (input rows past the last consumed tap
    receive no gradient — they are structurally zero)."""
    idx = [slice(None)]
    widths = [(0, 0)]
    for (lo, _), size, dim in zip(pads3, sizes3, arr.shape[1:4]):
        stop = min(lo + size, dim)
        idx.append(slice(lo, stop))
        widths.append((0, lo + size - stop))
    idx.append(slice(None))
    widths.append((0, 0))
    out = arr[tuple(idx)]
    if any(hi for _, hi in widths):
        out = jnp.pad(out, widths)
    return out


def _conv_core(x3, w3, stride3, kernel3, block_ci, block_co, interpret,
               dtile, n_dtiles, out_dtype, dilation3=None, groups=1,
               scale=None, bias=None, activation="none", alpha=0.2):
    """Pad channels/weights/leading dim and invoke the conv kernel ONCE.

    ``x3`` is the already (lo, hi)-padded canonical input.  The leading dim
    is aligned to ``n_dtiles * dtile * S_d`` rows — padded up, or cropped
    when the true extent leaves unconsumed remainder rows (any output row
    reads input rows strictly below ``(O - 1) * S_d + K_eff``, which the
    planner's halo slack always covers).  ``w3`` is ``[*K, Ci/G, Co]``:
    the contracted dim is already per-group, the produced dim (and x's
    channels, and the bias) pad PER GROUP so the kernel's group-blocked
    channel grid stays aligned.  Output is cropped by the caller.
    """
    ip = x3.shape[1]
    dilation3 = tuple(dilation3) if dilation3 is not None else (1, 1, 1)
    k_eff = _common.effective_kernel(kernel3, dilation3)
    o_lead, = conv_output_shape((ip,), (kernel3[0],), (stride3[0],),
                                dilation=(dilation3[0],))
    x3 = _common.pad_group_axis(x3, -1, groups, block_ci)
    # channel swap: the conv kernel contracts the TRAILING weight dim
    w3t = jnp.swapaxes(w3, -1, -2)                      # [*K, co, ci/G]
    w3t = _common.pad_group_axis(
        _common.pad_axis_to(w3t, -1, block_ci), -2, groups, block_co)
    w_taps = _common.phase_major_weights(w3t, kernel3, stride3, dilation3)
    if scale is not None:
        co = w3.shape[-1]
        scale = _common.pad_group_axis(
            jnp.broadcast_to(scale, (co,)).reshape(-1), 0, groups, block_co)
    if bias is not None:
        bias = _common.pad_group_axis(bias.reshape(-1), 0, groups, block_co)
    d_pad = n_dtiles * dtile * stride3[0]
    assert d_pad >= (o_lead - 1) * stride3[0] + k_eff[0], \
        (d_pad, o_lead, stride3, kernel3, dilation3)
    if d_pad >= ip:
        x3 = jnp.pad(x3, [(0, 0), (0, d_pad - ip)] + [(0, 0)] * 3)
    else:
        x3 = x3[:, :d_pad]          # remainder rows no output row consumes
    return _ck.conv_pallas_3d(
        x3, w_taps, kernel=kernel3, stride=stride3,
        block_ci=min(block_ci, x3.shape[-1]),
        block_co=min(block_co, w_taps.shape[1]),
        dtile=dtile, dilation=dilation3, groups=groups,
        scale=scale, bias=bias, activation=activation, alpha=alpha,
        interpret=interpret, out_dtype=out_dtype)


def _conv_fwd_impl(x, w, b, w_scale, stride, padding, dilation, groups,
                   activation, alpha, engine):
    cfg = engine.config
    interpret = cfg.pallas_interpret
    rank = x.ndim - 2
    stride_r = _canon(stride, rank)
    pads_r = canon_padding(padding, rank)
    dil_r = _common.canon_dilation(dilation, rank)
    x3, w3, stride3, squeeze = _common.lift_3d(x, w, stride_r)
    pads3 = _lift_padding(pads_r, rank)
    x3 = jnp.pad(x3, [(0, 0), *pads3, (0, 0)])
    kernel3 = w3.shape[:3]
    dilation3 = _common.lift_tuple3(dil_r, rank)
    co = w3.shape[-1]
    out3 = conv_output_shape(x3.shape[1:4], kernel3, stride3,
                             dilation=dilation3)

    plan = engine.plan("conv", x3.shape[1:4], kernel3, stride3,
                       x3.shape[-1], co, groups=groups, dilation=dilation3,
                       in_dtype_bytes=_common.operand_plan_bytes(x3.dtype),
                       w_dtype_bytes=_common.operand_plan_bytes(w3.dtype))
    if cfg.preferred_element_type is not None:
        out_dtype = cfg.preferred_element_type
    elif jnp.issubdtype(x.dtype, jnp.inexact):
        out_dtype = x.dtype
    else:
        out_dtype = jnp.float32         # quantized inputs store float
    y3 = _conv_core(x3, w3, stride3, kernel3, plan.block_ci, plan.block_co,
                    interpret, plan.dtile, plan.n_dtiles, out_dtype,
                    dilation3=dilation3, groups=groups,
                    scale=w_scale, bias=b,
                    activation=activation, alpha=alpha)
    y3 = _common.crop_group_axis(y3[:, :out3[0]], -1, groups, co // groups)
    return jnp.squeeze(y3, axis=squeeze) if squeeze else y3


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _conv(x, w, b, w_scale, stride, padding, dilation, groups, activation,
          alpha, engine):
    return _conv_fwd_impl(x, w, b, w_scale, stride, padding, dilation,
                          groups, activation, alpha, engine)


def _fwd(x, w, b, w_scale, stride, padding, dilation, groups, activation,
         alpha, engine):
    y = _conv(x, w, b, w_scale, stride, padding, dilation, groups,
              activation, alpha, engine)
    # activation gradients are recoverable from the OUTPUT, so y is the
    # only extra residual — and only when an activation is actually fused
    return y, (x, w, b, w_scale, y if activation != "none" else None)


def _bwd(stride, padding, dilation, groups, activation, alpha, engine,
         res, dy):
    """Training backward, fully on the uniform Pallas grid.

    Conv's adjoint is a deconv, so both cotangents reuse the DECONV
    subsystem's kernels with the channel roles swapped: ``dx`` is the
    deconv-forward kernel run on dy (windowed back through the (lo, hi)
    padding), ``dw`` the deconv dw kernel with dy playing the
    stride-1-indexed role.  One cached ``engine.plan("conv", ...,
    backward=True)`` decision budgets both working sets alongside the
    forward's.  The fused epilogue peels off first (activation gradient
    from the saved output, bias cotangent by reduction); grouped layers
    reshuffle the weight layout so each adjoint contracts only within its
    own group slab.

    Quantized-weight forwards stay f32-exact here: the backward runs on
    the DEQUANTIZED weights ``w * w_scale`` (the per-cout scale commutes
    with the adjoint contractions); int8 weights get a float0 cotangent
    and the scale's cotangent folds the dequantized-weight gradient back
    per channel — identical policy to the deconv op.
    """
    x, w, b, w_scale, y = res
    if jnp.issubdtype(x.dtype, jnp.integer):
        raise NotImplementedError(
            "backward through quantized activations is not supported; "
            "train with Precision(act_quant='none')")
    if w_scale is not None:
        wq, w = w, (w.astype(jnp.float32) * w_scale).astype(jnp.float32)
    cfg = engine.config
    interpret = cfg.pallas_interpret
    rank = x.ndim - 2
    stride_r = _canon(stride, rank)
    pads_r = canon_padding(padding, rank)
    dil_r = _common.canon_dilation(dilation, rank)

    if activation != "none":
        dy = dy * _common.activation_grad_from_output(y, activation, alpha)
    db = (dy.sum(axis=tuple(range(dy.ndim - 1))).astype(b.dtype)
          if b is not None else None)

    x3, w3, stride3, squeeze = _common.lift_3d(x, w, stride_r)
    dy3 = jnp.expand_dims(dy, squeeze) if squeeze else dy
    pads3 = _lift_padding(pads_r, rank)
    kernel3 = w3.shape[:3]
    dilation3 = _common.lift_tuple3(dil_r, rank)
    ci, co = x3.shape[-1], w3.shape[-1]
    cig, cog = ci // groups, co // groups
    in_p3 = tuple(i + lo + hi
                  for i, (lo, hi) in zip(x3.shape[1:4], pads3))
    out3 = conv_output_shape(in_p3, kernel3, stride3, dilation=dilation3)

    plan = engine.plan("conv", in_p3, kernel3, stride3, ci, co,
                       groups=groups, dilation=dilation3, backward=True)

    # dx: deconv of dy on the same grid.  _core_call's (block_ci, block_co)
    # are ITS input/output channel blocks — dy carries conv's Cout and the
    # result conv's Cin, hence the swap; likewise the weights go in as
    # [*K, Cout/G, G*Cin/G] (contract Co within each group, produce ALL
    # Ci group-major so _core_call's group-blocked maps stay aligned).
    w3dx = w3.reshape(*kernel3, cig, groups, cog).transpose(0, 1, 2, 5, 4, 3)
    w3dx = w3dx.reshape(*kernel3, cog, groups * cig)
    dx_full = _dops._core_call(
        dy3, w3dx, stride3, kernel3,
        plan.block_co, plan.block_ci, interpret,
        dtile=plan.dtile, n_dtiles=plan.n_dtiles, out_dtype=x.dtype,
        dilation3=dilation3, groups=groups)
    dx3 = _window(dx_full, pads3, x3.shape[1:4])
    dx = jnp.squeeze(dx3, axis=squeeze) if squeeze else dx3

    # dw: the deconv dw kernel with (x, dy) roles swapped — dy is the
    # stride-1-indexed array, the padded input the strided one.
    d_rows = plan.n_dtiles * plan.dtile
    x3f = jnp.pad(x3, [(0, 0), *pads3, (0, 0)])
    x3f = _common.pad_group_axis(x3f, -1, groups, plan.block_ci)
    d_pad_in = d_rows * stride3[0]
    if d_pad_in >= x3f.shape[1]:
        x3f = jnp.pad(x3f, [(0, 0), (0, d_pad_in - x3f.shape[1])]
                      + [(0, 0)] * 3)
    else:
        x3f = x3f[:, :d_pad_in]
    dy3p = _common.pad_group_axis(dy3, -1, groups, plan.block_co)
    dy3p = jnp.pad(dy3p, [(0, 0), (0, d_rows - out3[0])] + [(0, 0)] * 3)
    dw3 = _dk.deconv_dw_pallas_3d(
        dy3p, x3f, kernel=kernel3, stride=stride3,
        block_ci=plan.block_co, block_co=plan.block_ci,
        dtile=plan.dtile, dilation=dilation3, groups=groups,
        interpret=interpret, out_dtype=w.dtype)
    # the kernel emits taps phase-major; invert back to kernel-element order
    inv = _common.phase_major_inverse(kernel3, stride3, dilation3)
    dw3 = _common.crop_group_axis(dw3[jnp.asarray(inv)][:, :cog], -1,
                                  groups, cig)          # [prod(K), co/G, ci]
    dw3 = dw3.reshape(*kernel3, cog, groups, cig).transpose(0, 1, 2, 5, 4, 3)
    dw = dw3.reshape(w.shape)
    if w_scale is None:
        return dx.astype(x.dtype), dw, db, None
    # dw above is the gradient of the DEQUANTIZED weight; chain back as in
    # the deconv op: per-channel fold for d(scale), float0 for int8 w.
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
    return dx.astype(x.dtype), dwq, db, dscale


_conv.defvjp(_fwd, _bwd)


def conv(x: jax.Array, w: jax.Array, stride=1, padding=0, *,
         dilation=1, groups: int = 1, bias: jax.Array | None = None,
         w_scale: jax.Array | None = None,
         activation: str = "none", alpha: float = 0.2,
         block_ci: int | None = None, block_co: int | None = None,
         interpret: bool | None = None,
         max_tile_bytes: int | None = None,
         preferred_element_type=None,
         engine=None) -> jax.Array:
    """Public op: uniform 1D/2D/3D strided convolution via the Pallas kernel.

    x: [N, *spatial, Cin]; w: [*K, Cin/groups, Cout]; semantics match
    ``lax.conv_general_dilated`` (correlation, channels-last,
    ``rhs_dilation=dilation``, ``feature_group_count=groups``): per-dim
    output extent ``(I + lo + hi - (K-1)*dilation - 1) // S + 1``.
    ``padding`` is a scalar, per-dim scalars, or per-dim ``(lo, hi)``
    pairs.  ``bias``/``activation`` fuse the layer epilogue into the
    kernel's accumulator flush — no separate elementwise pass is traced.
    ``w_scale`` (per-cout, shape ``(Cout,)`` or scalar) marks ``w`` as
    scaled — typically int8 from ``repro.quant.quantize_weights`` — and
    fuses the dequant multiply into that same epilogue, scale → bias →
    activation, on the f32 accumulator.

    The tuning keywords are compatibility sugar: they resolve to a memoized
    ``repro.core.engine.default_engine`` whose ``EngineConfig`` carries
    them, so repeated calls share one plan cache.  Passing ``engine=``
    directly (what ``UniformEngine.conv`` does) is the configured path —
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
    return _conv(x, w, bias, w_scale, _canon(stride, rank),
                 canon_padding(padding, rank),
                 _common.canon_dilation(dilation, rank), groups,
                 activation, float(alpha), engine)
