"""Pallas TPU kernel: first-class strided convolution on the uniform grid.

PR 2 proved the deconv grid is bidirectional: the deconv backward's dx
kernel IS a stride-S convolution of dy.  This module promotes that body out
of its backward-only role into the engine's forward convolution — the other
half of the paper's "uniform architecture" story (one PE mesh serving convs
AND deconvs, cf. Bai et al. 2020).  ``kernels.deconv.kernel`` keeps
``deconv_dx_pallas_3d`` as a thin channel-swapped wrapper over this kernel,
so there is exactly ONE strided-conv body in the tree.

Same fused 4D grid as the deconv forward:

    grid = (N, Cout/block_co, n_dtiles, Cin/block_ci)

  * the two leading dims are parallel; the trailing two sequential.  The
    innermost Cin dim is the paper's adder tree — partial sums accumulate
    into an f32 VMEM scratch across Cin blocks.
  * y[o] = sum_k x[o*S + k] · w[k] (VALID, correlation convention — the
    caller pads (lo, hi) host-side).  Taps are gathered from the S^d *input*
    phases of x, split outside the kernel (``kernels.common.to_phases``):
    phase ``x_ph = x[p::S]``, a flattened [dtile*Lh*Lw, bci] slab, feeds ONE
    wide MXU matmul against the phase's valid taps (phase-major weight
    layout) — S^d dispatches per grid step, not K^d.  Stride 1 is the
    degenerate single phase (one matmul carrying all K^d taps).  When the
    output block is under 128 lanes, a phase's in-plane taps move into the
    contraction instead (``kernels.common.fold_in_plane``): only its
    leading-dim taps overlap-add into the accumulator.
  * each grid tile owns ``dtile`` output rows and reads the aligned
    ``dtile`` rows of every input phase; when K_d > S_d a tap reaches into
    the NEXT tile's input slab, so the d-tile axis iterates in REVERSE and
    the spill rides a VMEM halo carry (the FIFO-D exchange running
    backward) — recursive, so K_d >> S_d*dtile composes.
  * 2D/1D are the degenerate singleton-dim cases; ``ops.py`` lifts inputs
    as [N, H, 1, W, C] so the large image dim lands on the tileable axis.

The caller (``kernels.conv.ops``) zero-pads the input's leading dim to
``n_dtiles * dtile * S_d`` rows with ``n_dtiles * dtile`` at least
``O_d + ceil(K_d/S_d) - 1`` (output rows plus halo slack), which keeps every
real tap in-slab and makes the final carry-out structurally zero; the
blocking decision comes from ``repro.core.tiling.plan_uniform_tiles(mode="conv")``.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    apply_epilogue,
    compiler_params,
    flat_grid,
    fold_in_plane,
    fold_split,
    fold_width,
    from_tiles,
    halo_depth,
    lift_geometry3,
    mxu_dtype,
    mxu_precision,
    phase_geometry,
    phase_taps,
    phase_weight_slab,
    tile_bytes,
    to_phases,
)


def _folded_weight_slab(w_ref, off: int, n_lead: int, n_plane: int, dtype):
    """A folded phase's taps as ONE [n_plane*a, n_lead*b] operand: block
    (j, i) is tap ``off + i*n_plane + j`` (see ``fold_split``), so the
    contraction runs over the in-plane taps and the columns over the
    leading-dim taps."""
    rows = [jnp.concatenate([w_ref[off + i * n_plane + j].astype(dtype)
                             for i in range(n_lead)], axis=1)
            for j in range(n_plane)]
    return jnp.concatenate(rows, axis=0)


def _conv_kernel_body(*refs, rows, plane, row_w, margin, dtile, halo,
                      kernel, stride, dilation, n_ci_blocks, out_dtype,
                      has_scale=False, has_bias=False, activation="none",
                      alpha=0.2):
    """One grid step: a (batch, co-block, d-tile, ci-block) partial conv.

    Blocks are flattened slabs on the tile's (Lh, Lw) output grid (see
    ``kernels.common``), ``plane = Lh*Lw`` rows per leading-dim row:

    x_ref:   [1, 1, prod(S), rows, bci]  (host-split input phases of tile t)
    w_ref:   [prod(K), bci, bco]         (phase-major tap order)
    s_ref:   [1, bco]                    (only when ``has_scale``)
    b_ref:   [1, bco]                    (only when ``has_bias``)
    o_ref:   [1, 1, rows, bco]           (this tile's output slab)
    acc_ref: VMEM f32 [margin + (dtile + M_d - 1)*plane, bco]
    halo_ref: VMEM f32 [(M_d - 1)*plane, bco] (only when M_d > 1)
    xcol_ref: VMEM [rows, n_plane*bci] MXU dtype (only when a phase folds)

    Input phase row ``u`` feeds output row ``u - m`` through tap ``m``:
    each phase is ONE matmul whose tap columns overlap-add at the row
    offset ``margin + (M_d-1-m_d)*plane - m_h*Lw - m_w`` (the ``margin``
    head keeps every offset non-negative; it only ever collects wrapped
    terms of padding positions).  Accumulator rows ``[margin, margin +
    (M_d-1)*plane)`` belong to the previous tile and ride the reversed
    FIFO-D carry.

    A phase that ``fold_in_plane`` selects (lane-thin ``bco``, several
    in-plane taps) moves its in-plane shifts to the input side:
    ``xcol_ref`` gathers the phase read at each in-plane shift
    ``m_h*Lw + m_w``, side by side, and the matmul contracts
    ``[rows, n_plane*bci]`` against ``[n_plane*bci, n_lead*bco]``, so only
    its ``n_lead`` leading-dim taps overlap-add.  A shifted read past the
    slab's end is zero-filled: those rows feed only outputs whose in-plane
    position lies past (OH, OW), which the host crops.

    The epilogue (scale + bias + activation) runs in ``_flush`` — after the
    Cin adder tree completes AND after the reversed FIFO-D carry-in, so it
    sees the finished f32 accumulation, never a partial sum.  int8 operands
    ride the same matmuls, cast to f32 in-register just before the dot
    (|q| <= 127, exact); the per-cout dequant scale multiplies the finished
    accumulator first thing in the epilogue.
    """
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    s_ref = next(it) if has_scale else None
    b_ref = next(it) if has_bias else None
    o_ref, acc_ref = next(it), next(it)
    halo_ref = next(it) if halo else None
    xcol_ref = next(it, None)
    r = pl.program_id(2)
    cb = pl.program_id(3)
    cdt = mxu_dtype(x_ref.dtype, w_ref.dtype)
    bco = acc_ref.shape[-1]

    @pl.when(cb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    off = 0
    for p_idx, _, taps in phase_taps(kernel, stride, dilation):
        if fold_in_plane(taps, bco):
            # [rows, n_plane*bci] x [n_plane*bci, n_lead*bco]: the phase
            # gathered at each in-plane shift, zero past the slab's end
            lead, in_plane = fold_split(taps)
            bci = x_ref.shape[-1]
            for j, (mh, mw) in enumerate(in_plane):
                cols = pl.ds(j * bci, bci)
                sh = mh * row_w + mw
                xcol_ref[pl.ds(0, rows - sh), cols] = \
                    x_ref[0, 0, p_idx, pl.ds(sh, rows - sh), :].astype(cdt)
                if sh:
                    xcol_ref[pl.ds(rows - sh, sh), cols] = \
                        jnp.zeros((sh, bci), cdt)
            x = xcol_ref[:, pl.ds(0, len(in_plane) * bci)]
            w = _folded_weight_slab(w_ref, off, len(lead), len(in_plane), cdt)
            out_taps = [(md, 0, 0) for md in lead]
        else:
            x = x_ref[0, 0, p_idx].astype(cdt)          # input phase p
            # one wide matmul per phase: [rows, bci] x [bci, n_taps*bco]
            w = phase_weight_slab(w_ref, off, len(taps), cdt)
            out_taps = taps
        off += len(taps)
        res = jnp.dot(x, w, preferred_element_type=jnp.float32,
                      precision=mxu_precision(cdt))
        for t_idx, m in enumerate(out_taps):
            # y[o] += x_p[o + m] w_tap: row u of the phase lands at u - m;
            # the leading shift reaches into the carry rows at the top
            at = margin + (halo - m[0]) * plane - m[1] * row_w - m[2]
            acc_ref[pl.ds(at, rows), :] += \
                res[:, t_idx * bco:(t_idx + 1) * bco]

    last = cb == n_ci_blocks - 1
    if halo:
        hp = halo * plane

        # reversed FIFO-D: the previous (reversed) step worked on tile t+1
        # and deposited its spill into THIS tile's tail rows ...
        @pl.when(jnp.logical_and(last, r > 0))
        def _carry_in():
            acc_ref[pl.ds(margin + dtile * plane, hp), :] += halo_ref[...]

        # ... and this tile's head rows (outputs of tile t-1, read AFTER the
        # carry-in so deep halos compose) are left for the next step.
        @pl.when(last)
        def _carry_out():
            halo_ref[...] = acc_ref[pl.ds(margin, hp), :]

    @pl.when(last)
    def _flush():
        y = apply_epilogue(acc_ref[pl.ds(margin + halo * plane, rows), :],
                           b_ref[...] if b_ref is not None else None,
                           activation, alpha,
                           scale=s_ref[...] if s_ref is not None else None)
        o_ref[0, 0] = y.astype(out_dtype)


def conv_pallas_3d(x: jax.Array, w_taps: jax.Array, *,
                   kernel: Sequence[int], stride: Sequence[int],
                   block_ci: int, block_co: int, dtile: int,
                   interpret: bool,
                   dilation: Sequence[int] | None = None,
                   groups: int = 1,
                   scale: jax.Array | None = None,
                   bias: jax.Array | None = None,
                   activation: str = "none", alpha: float = 0.2,
                   out_dtype=None) -> jax.Array:
    """Uniform strided conv on rank-3 canonical layout — one ``pallas_call``.

    x: [N, n_dtiles*dtile*S_d, IH, IW, Ci] — the (lo, hi)-padded input,
    zero-padded on the leading dim to the tile grid (ops.py pads); trailing
    extents are consumed VALID, so OH/OW = (I - K_eff)//S + 1 statically.
    w_taps: [prod(K), Co, Ci/G] in the phase-major tap order of
    ``kernels.common.phase_major_tap_index`` (ops.py gathers it), output
    channels leading — the contraction runs over the trailing per-group Ci.
    ``groups`` blocks the channel grid per group: the co grid dim still
    enumerates ALL output blocks while the inner ci dim spans one group's
    input blocks, and the x index map routes each output block to its
    group's input slab — grouped/depthwise layers stay ONE pallas_call.
    ``bias``/``activation`` fuse the layer epilogue into the kernel flush.
    Returns [N, n_dtiles*dtile, OH, OW, Co]; rows at or beyond the true
    output extent are cropped by the caller.  The stride-phase split of x
    and the flattening around the kernel are plain XLA reshapes.
    """
    n, d_in, ih, iw, ci = x.shape
    co = w_taps.shape[1]
    kernel = tuple(kernel)
    stride = tuple(stride)
    dilation = tuple(dilation) if dilation is not None else (1,) * len(kernel)
    k_eff = tuple((k - 1) * d + 1 for k, d in zip(kernel, dilation))
    if out_dtype is None:
        # quantized inputs never store quantized: default to the f32 acc
        out_dtype = x.dtype if jnp.issubdtype(x.dtype, jnp.inexact) \
            else jnp.float32
    assert d_in % (dtile * stride[0]) == 0, (d_in, dtile, stride)
    n_dt = d_in // (dtile * stride[0])
    oh = (ih - k_eff[1]) // stride[1] + 1
    ow = (iw - k_eff[2]) // stride[2] + 1
    assert ci % groups == 0 and co % groups == 0, (ci, co, groups)
    cig = ci // groups
    assert cig % block_ci == 0 and co % block_co == 0, (ci, co,
                                                        block_ci, block_co)
    n_ci, n_co = cig // block_ci, co // block_co
    assert n_co % groups == 0, (n_co, groups)
    nco_g = n_co // groups              # output blocks per group
    m_max = phase_geometry(kernel, stride, dilation)
    halo = halo_depth(kernel, stride, dilation)
    grid_hw = flat_grid((oh, ow), m_max[1:])
    plane = grid_hw[0] * grid_hw[1]
    rows = dtile * plane
    margin = (m_max[1] - 1) * grid_hw[1] + m_max[2] - 1

    body = functools.partial(
        _conv_kernel_body, rows=rows, plane=plane, row_w=grid_hw[1],
        margin=margin, dtile=dtile, halo=halo, kernel=kernel, stride=stride,
        dilation=dilation, n_ci_blocks=n_ci, out_dtype=out_dtype,
        has_scale=scale is not None, has_bias=bias is not None,
        activation=activation, alpha=alpha)
    scratch = [pltpu.VMEM((margin + (dtile + halo) * plane, block_co),
                          jnp.float32)]
    if halo:
        scratch.append(pltpu.VMEM((halo * plane, block_co), jnp.float32))
    n_plane = fold_width(kernel, stride, dilation, block_co)
    if n_plane:
        scratch.append(pltpu.VMEM((rows, n_plane * block_ci),
                                  mxu_dtype(x.dtype, w_taps.dtype)))

    in_specs = [
        pl.BlockSpec((1, 1, math.prod(stride), rows, block_ci),
                     lambda b, oc, t, ic: (b, n_dt - 1 - t, 0, 0,
                                           (oc // nco_g) * n_ci + ic)),
        pl.BlockSpec((math.prod(kernel), block_ci, block_co),
                     lambda b, oc, t, ic: (0, ic, oc)),
    ]
    # the kernel contracts the MIDDLE weight dim: [prod(K), Ci/G, Co]
    operands = [to_phases(x, stride, n_dt, grid_hw),
                jnp.swapaxes(w_taps, 1, 2)]
    if scale is not None:
        in_specs.append(pl.BlockSpec((1, block_co),
                                     lambda b, oc, t, ic: (0, oc)))
        operands.append(scale.reshape(1, co).astype(jnp.float32))
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, block_co),
                                     lambda b, oc, t, ic: (0, oc)))
        operands.append(bias.reshape(1, co))

    step = vmem_bytes((n_dt * dtile, oh, ow), kernel, stride, block_ci,
                      block_co, jnp.dtype(x.dtype).itemsize, dtile=dtile,
                      dilation=dilation,
                      w_dtype_bytes=jnp.dtype(w_taps.dtype).itemsize,
                      out_dtype_bytes=jnp.dtype(out_dtype).itemsize)
    y = pl.pallas_call(
        body,
        grid=(n, n_co, n_dt, n_ci),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rows, block_co),
                               lambda b, oc, t, ic: (b, n_dt - 1 - t, 0, oc)),
        out_shape=jax.ShapeDtypeStruct((n, n_dt, rows, co), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=compiler_params(
            step, ("parallel", "parallel", "arbitrary", "arbitrary")),
    )(*operands)
    return from_tiles(y, grid_hw)[:, :, :oh, :ow]


def vmem_bytes(out_spatial, kernel, stride, block_ci, block_co,
               in_dtype_bytes: int = 2, dtile: int | None = None,
               dilation=None, w_dtype_bytes: int | None = None,
               out_dtype_bytes: int | None = None) -> int:
    """Static per-grid-step VMEM footprint of ``conv_pallas_3d``.

    ``out_spatial`` is the conv OUTPUT extent per dim (the quantity the
    leading-dim tiling counts).  Models, in Mosaic's tiled layout
    (``kernels.common.tile_bytes``), the double-buffered phase-split input,
    weight and output blocks, the f32 accumulator + halo carry, and the
    widest phase's f32 matmul result (every tap on its columns, or only
    the leading-dim taps of a phase ``fold_in_plane`` selects), plus the
    ``[rows, n_plane*bci]`` gather scratch when a phase folds.  Dilation
    widens the phase geometry through the effective kernel extent.  The deconv
    backward's dx budget is this same model with the channel roles swapped
    (see ``kernels.deconv.kernel.vmem_bytes_bwd``).  ``w_dtype_bytes`` /
    ``out_dtype_bytes`` default to ``in_dtype_bytes``; quantized plans pass
    1 for int8 operands.
    """
    w_dtype_bytes = in_dtype_bytes if w_dtype_bytes is None else w_dtype_bytes
    out_dtype_bytes = in_dtype_bytes if out_dtype_bytes is None \
        else out_dtype_bytes
    # the in-kernel matmul operand width: int8 widens to f32 (mxu_dtype)
    mxu_bytes = (4 if min(in_dtype_bytes, w_dtype_bytes) == 1
                 else max(in_dtype_bytes, w_dtype_bytes))
    out_spatial, kernel, stride, dilation = lift_geometry3(
        out_spatial, kernel, stride, dilation)
    m_max = phase_geometry(kernel, stride, dilation)
    halo = m_max[0] - 1
    if dtile is None:
        dtile = out_spatial[0] + halo
    grid_hw = flat_grid(tuple(out_spatial[1:]), m_max[1:])
    plane = grid_hw[0] * grid_hw[1]
    rows = dtile * plane
    margin = (m_max[1] - 1) * grid_hw[1] + m_max[2] - 1
    res_cols = max(len(fold_split(taps)[0]) if fold_in_plane(taps, block_co)
                   else len(taps)
                   for _, _, taps in phase_taps(kernel, stride, dilation))
    n_plane = fold_width(kernel, stride, dilation, block_co)
    return (2 * math.prod(stride) * tile_bytes(rows, block_ci,
                                               in_dtype_bytes)
            + 2 * math.prod(kernel) * tile_bytes(block_ci, block_co,
                                                 w_dtype_bytes)
            + 2 * tile_bytes(rows, block_co, out_dtype_bytes)
            + tile_bytes(margin + (dtile + halo) * plane, block_co, 4)
            + (tile_bytes(halo * plane, block_co, 4) if halo else 0)
            + tile_bytes(rows, res_cols * block_co, 4)
            + (tile_bytes(rows, n_plane * block_ci, mxu_bytes)
               if n_plane else 0))
