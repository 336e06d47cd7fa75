"""Pallas TPU kernel: uniform 2D/3D IOM deconvolution (polyphase form).

Maps the paper's PE mesh onto the TPU memory hierarchy with a fused 4D grid

    grid = (N, Cout/block_co, n_dtiles, Cin/block_ci)

  * the two leading dimensions are parallel (independent batch / out-channel
    blocks); the two trailing ones are sequential.  The innermost Cin
    dimension is the paper's adder tree — partial products accumulate into a
    VMEM f32 scratch (`@pl.when(ci == 0)` zero-init, write-out at the last
    Cin step).
  * the leading spatial dim is blocked into ``n_dtiles`` tiles of ``dtile``
    input rows each, all served by this single ``pallas_call``: the paper's
    spatial blocking (Tz/Tr/Tc) lives *inside* the accelerator grid instead
    of a Python loop around it.
  * adjacent d-tiles overlap in the output by ``ceil(K_d/S_d) - 1`` phase
    rows.  That overlap — the paper's FIFO-D exchange between PE planes — is
    carried through a VMEM halo scratch: tile ``t`` overlap-adds the tail of
    tile ``t-1`` into the head of its accumulator and deposits its own tail
    for tile ``t+1``.  The carry composes recursively, so halos deeper than
    one tile (K_d ≫ S_d·dtile) propagate correctly.  Each tile then owns a
    disjoint ``dtile·S_d``-row slab of the output: no HBM round-trip, no
    outside stitching.
  * every block is a flattened 2-D slab: a tile's trailing spatial dims sit
    zero-padded on an (Lh, Lw) grid, ``[dtile*Lh*Lw, C]``, the layout
    Mosaic lowers without reshapes (``kernels.common``).
  * ONE tap-batched MXU matmul per phase: the phase's valid taps fold into
    the weight columns, so the x slab [dtile*Lh*Lw, bci] contracts against
    [bci, n_taps*bco] in a single dispatch — S^d wide matmuls per grid step
    instead of K^d small ones (e.g. 27 -> 8 for 3³/s2, 25 -> 4 for 5²/s2).
    Taps across all phases still number exactly K^d — the IOM valid-MAC
    count; no inserted zero is ever touched.
  * the in-tile overlap-add (paper: FIFO-V/H exchange) is a shifted in-VMEM
    accumulation into the per-phase buffer — one static row offset per tap
    on the flattened grid; the kernel writes its output phase-major and
    the phases interleave outside the kernel (one XLA transpose).
  * the TRAINING backward pass runs on the same uniform grid: deconv's
    adjoint is a strided convolution — which since PR 3 is the engine's
    first-class forward conv (``kernels.conv.kernel.conv_pallas_3d``).
    ``deconv_dx_pallas_3d`` is the channel-role-swapped wrapper over it
    (taps gathered from dy's S^d input phases, d-tile axis iterated in
    reverse so the halo carry flows backward); ``deconv_dw_pallas_3d``
    accumulates per-tap [bci, bco] contractions across the sequential
    (N, d-tile) grid dims into an f32 VMEM scratch, carrying the last
    M_d - 1 x rows so cross-tile pairs never leave VMEM.
  * 2D is the degenerate case of a singleton middle dim (depth phase/tap
    loops statically collapse — the paper's "FIFO-D disabled"); ``ops.py``
    lifts 2D inputs as [N, H, 1, W, C] so the large image dim lands on the
    tileable leading axis.
  * the dx body is the conv kernel's; dx and dw read dy split into its S^d
    phases outside the kernel, so no kernel strides a value.

The caller (``ops.py``) zero-pads the leading dim to ``n_dtiles * dtile``
with at least ``ceil(K_d/S_d) - 1`` rows of slack, which makes the final
tile's carry-out provably zero; the blocking decision itself comes from the
unified planner in ``repro.core.tiling.plan_uniform_tiles``.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared polyphase geometry (also served to kernels.conv); the old private
# names are kept as aliases for in-repo callers.
from repro.kernels.common import (  # noqa: F401
    apply_epilogue,
    compiler_params,
    flat_grid,
    from_phases,
    halo_depth,
    lift_geometry3,
    mxu_dtype,
    mxu_precision,
    phase_geometry as _phase_geometry,
    phase_major_tap_index,
    phase_taps as _phase_taps,
    phase_weight_slab,
    tile_bytes,
    to_phases,
    to_tiles,
)


def _deconv_kernel_body(*refs, rows, plane, row_w, dtile, halo, kernel,
                        stride, dilation, n_ci_blocks, out_dtype,
                        has_scale=False, has_bias=False,
                        activation="none", alpha=0.2):
    """One grid step: accumulate a (batch, co-block, d-tile, ci-block) part.

    Every block is a flattened slab on the tile's (Lh, Lw) grid (see
    ``kernels.common``), ``plane = Lh*Lw`` rows per leading-dim row:

    x_ref:   [1, 1, rows, bci]            (rows = dtile*plane; zero-padded)
    w_ref:   [prod(K), bci, bco]          (phase-major tap order)
    s_ref:   [1, bco]                     (only when ``has_scale``)
    b_ref:   [1, bco]                     (only when ``has_bias``)
    o_ref:   [1, 1, prod(S), rows, bco]   (phase-major; the host interleaves)
    acc_ref: VMEM f32 [prod(S), (dtile+M_d-1)*plane + tail, bco]
    halo_ref: VMEM f32 [prod(S), (M_d-1)*plane, bco] (None if M_d == 1)

    Tap ``m`` of phase ``p`` overlap-adds ``x @ w[k]`` into the phase
    accumulator at the single row offset ``m_d*plane + m_h*Lw + m_w``.
    Under dilation a tap carries kernel element ``k = (m*S + p)/dil``;
    phases no kernel element lands in are structural zeros — their
    accumulator rows stay zero and come out as genuine zero output rows.
    The fused epilogue runs at ``_flush`` on the completed f32 accumulation
    (after the FIFO-D carry-in).

    Quantized operands (int8 x and/or w) ride the SAME matmuls: they are
    cast to f32 in-register right before the dot (|q| <= 127, so the cast
    is exact) and the per-cout dequant scale ``s_ref`` multiplies the
    completed accumulator first thing in the fused epilogue — the scale
    commutes with the ci/tap contraction, so fusing it there is exact.
    """
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    s_ref = next(it) if has_scale else None
    b_ref = next(it) if has_bias else None
    o_ref, acc_ref = next(it), next(it)
    halo_ref = next(it, None)
    dt = pl.program_id(2)
    ci = pl.program_id(3)
    cdt = mxu_dtype(x_ref.dtype, w_ref.dtype)
    bco = acc_ref.shape[-1]

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0, 0].astype(cdt)                     # [rows, bci]
    off = 0
    for p_idx, _, taps in _phase_taps(kernel, stride, dilation):
        # Tap-batched MXU dispatch: the phase's valid taps sit contiguously
        # in the phase-major weight layout, so ONE [rows, bci] @
        # [bci, n_taps*bco] matmul serves the whole phase (S^d dispatches
        # per grid step instead of K^d).  The column groups are then
        # distributed into the shifted overlap-add slices (VPU adds).
        w = phase_weight_slab(w_ref, off, len(taps), cdt)
        off += len(taps)
        res = jnp.dot(x, w, preferred_element_type=jnp.float32,
                      precision=mxu_precision(cdt))
        for t_idx, m in enumerate(taps):
            # overlap-add: y_p[q] += x[q - m] * w_tap  ->  row offset m
            at = m[0] * plane + m[1] * row_w + m[2]
            acc_ref[p_idx, pl.ds(at, rows), :] += \
                res[:, t_idx * bco:(t_idx + 1) * bco]

    last = ci == n_ci_blocks - 1
    if halo:
        hp = halo * plane

        # FIFO-D exchange, in-grid: the previous tile's tail rows
        # overlap-add into the head of this tile's accumulator ...
        @pl.when(jnp.logical_and(last, dt > 0))
        def _carry_in():
            acc_ref[:, pl.ds(0, hp), :] += halo_ref[...]

        # ... and this tile's tail (read AFTER the carry-in, so halos
        # deeper than one tile compose recursively) is left for the next.
        @pl.when(last)
        def _carry_out():
            halo_ref[...] = acc_ref[:, pl.ds(dtile * plane, hp), :]

    @pl.when(last)
    def _flush():
        # owned rows only; the tail rides the halo
        scale = s_ref[...] if s_ref is not None else None
        bias = b_ref[...] if b_ref is not None else None
        for p in range(acc_ref.shape[0]):
            y = apply_epilogue(acc_ref[p, pl.ds(0, rows), :], bias,
                               activation, alpha, scale=scale)
            o_ref[0, 0, p] = y.astype(out_dtype)


def deconv_pallas_3d(x: jax.Array, w_taps: jax.Array, *,
                     kernel: Sequence[int], stride: Sequence[int],
                     block_ci: int, block_co: int,
                     interpret: bool,
                     dtile: int | None = None,
                     dilation: Sequence[int] | None = None,
                     groups: int = 1,
                     scale: jax.Array | None = None,
                     bias: jax.Array | None = None,
                     activation: str = "none", alpha: float = 0.2,
                     out_dtype=None) -> jax.Array:
    """Uniform deconv on rank-3 canonical layout — one call, any input size.

    x: [N, D_pad, H, W, Ci] with ``D_pad`` a multiple of ``dtile``
    (``dtile=None`` means one tile spanning the whole leading dim);
    w_taps: [prod(K), Ci, Co] in the phase-major tap order of
    ``phase_major_tap_index`` (ops.py gathers it), so each phase's taps are
    one contiguous slice.  Channels must divide the blocks (ops.py pads).

    Whenever K_d > S_d the caller must zero-pad the true leading extent D by
    at least ``ceil(K_d/S_d) - 1`` rows (ops.py always pads to
    ``n_dtiles * dtile >= D + ceil(K_d/S_d) - 1``): that guarantees every
    real output row lands inside the returned [N, D_pad*S_d, OH, OW, Co]
    extent and the last tile's halo carry-out is structurally zero.  Rows at
    or beyond (D-1)*S_d + K_d are zero and cropped by the caller.

    The kernel works on flattened tiles and writes phase-major output; the
    host-side flattening and the phase interleave are plain XLA reshapes
    around the single ``pallas_call``.
    """
    n, d_pad, h, wdim, ci = x.shape
    co = w_taps.shape[-1]
    kernel = tuple(kernel)
    stride = tuple(stride)
    dilation = tuple(dilation) if dilation is not None else (1,) * len(kernel)
    k_eff = tuple((k - 1) * d + 1 for k, d in zip(kernel, dilation))
    if out_dtype is None:
        # quantized inputs never store quantized: default to the f32 acc
        out_dtype = x.dtype if jnp.issubdtype(x.dtype, jnp.inexact) \
            else jnp.float32
    if dtile is None:
        dtile = d_pad
    assert d_pad % dtile == 0, (d_pad, dtile)
    n_dt = d_pad // dtile
    assert ci % groups == 0 and co % groups == 0, (ci, co, groups)
    cig = ci // groups
    assert cig % block_ci == 0 and co % block_co == 0, (ci, co,
                                                        block_ci, block_co)
    n_ci, n_co = cig // block_ci, co // block_co
    assert n_co % groups == 0, (n_co, groups)
    nco_g = n_co // groups              # output blocks per group

    m_max = _phase_geometry(kernel, stride, dilation)
    halo = halo_depth(kernel, stride, dilation)
    grid_hw = flat_grid((h, wdim), m_max[1:])
    plane = grid_hw[0] * grid_hw[1]
    rows = dtile * plane
    tail = (m_max[1] - 1) * grid_hw[1] + m_max[2] - 1
    n_phases = math.prod(stride)
    out_trailing = tuple((i - 1) * s + k for i, s, k in
                         zip((h, wdim), stride[1:], k_eff[1:]))

    body = functools.partial(
        _deconv_kernel_body, rows=rows, plane=plane, row_w=grid_hw[1],
        dtile=dtile, halo=halo, kernel=kernel, stride=stride,
        dilation=dilation, n_ci_blocks=n_ci, out_dtype=out_dtype,
        has_scale=scale is not None, has_bias=bias is not None,
        activation=activation, alpha=alpha)

    scratch = [pltpu.VMEM((n_phases, (dtile + halo) * plane + tail,
                           block_co), jnp.float32)]
    if halo:
        scratch.append(
            pltpu.VMEM((n_phases, halo * plane, block_co), jnp.float32))

    in_specs = [
        pl.BlockSpec((1, 1, rows, block_ci),
                     lambda b, oc, dt, ic: (b, dt, 0,
                                            (oc // nco_g) * n_ci + ic)),
        pl.BlockSpec((math.prod(kernel), block_ci, block_co),
                     lambda b, oc, dt, ic: (0, ic, oc)),
    ]
    operands = [to_tiles(x, n_dt, grid_hw), w_taps]
    if scale is not None:
        in_specs.append(pl.BlockSpec((1, block_co),
                                     lambda b, oc, dt, ic: (0, oc)))
        operands.append(scale.reshape(1, co).astype(jnp.float32))
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, block_co),
                                     lambda b, oc, dt, ic: (0, oc)))
        operands.append(bias.reshape(1, co))

    step = vmem_bytes((d_pad, h, wdim), kernel, stride, block_ci, block_co,
                      jnp.dtype(x.dtype).itemsize, dtile=dtile,
                      dilation=dilation,
                      w_dtype_bytes=jnp.dtype(w_taps.dtype).itemsize,
                      out_dtype_bytes=jnp.dtype(out_dtype).itemsize)
    y = pl.pallas_call(
        body,
        grid=(n, n_co, n_dt, n_ci),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, n_phases, rows, block_co),
                               lambda b, oc, dt, ic: (b, dt, 0, 0, oc)),
        out_shape=jax.ShapeDtypeStruct((n, n_dt, n_phases, rows, co),
                                       out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=compiler_params(
            step, ("parallel", "parallel", "arbitrary", "arbitrary")),
    )(*operands)
    y = from_phases(y, stride, grid_hw)
    return y[:, :, :out_trailing[0], :out_trailing[1]]


def vmem_bytes(in_spatial, kernel, stride, block_ci, block_co,
               in_dtype_bytes: int = 2, dtile: int | None = None,
               dilation=None, w_dtype_bytes: int | None = None,
               out_dtype_bytes: int | None = None) -> int:
    """Static VMEM footprint of one grid step (for the tiling planner).

    Counts what ``deconv_pallas_3d`` allocates in Mosaic's tiled layout
    (``kernels.common.tile_bytes``: lanes pad to 128, so thin channel
    blocks pay for full lanes): the double-buffered input, weight and
    phase-major output blocks, the f32 accumulator and halo carry, and the
    widest phase's tap-batched matmul result.  ``dtile=None`` is one
    tile over the whole leading dim plus its halo slack.  Dilation widens
    the phase geometry through the effective kernel extent.
    ``w_dtype_bytes`` / ``out_dtype_bytes`` default to ``in_dtype_bytes``;
    quantized plans pass 1 for int8 operands.
    """
    w_dtype_bytes = in_dtype_bytes if w_dtype_bytes is None else w_dtype_bytes
    out_dtype_bytes = in_dtype_bytes if out_dtype_bytes is None \
        else out_dtype_bytes
    in_spatial, kernel, stride, dilation = lift_geometry3(
        in_spatial, kernel, stride, dilation)
    m_max = _phase_geometry(kernel, stride, dilation)
    halo = m_max[0] - 1
    if dtile is None:
        dtile = in_spatial[0] + halo
    grid_hw = flat_grid(tuple(in_spatial[1:]), m_max[1:])
    plane = grid_hw[0] * grid_hw[1]
    rows = dtile * plane
    tail = (m_max[1] - 1) * grid_hw[1] + m_max[2] - 1
    n_ph = math.prod(stride)
    taps = math.prod(m_max)
    return (2 * tile_bytes(rows, block_ci, in_dtype_bytes)
            + 2 * math.prod(kernel) * tile_bytes(block_ci, block_co,
                                                 w_dtype_bytes)
            + 2 * n_ph * tile_bytes(rows, block_co, out_dtype_bytes)
            + n_ph * tile_bytes((dtile + halo) * plane + tail, block_co, 4)
            + (n_ph * tile_bytes(halo * plane, block_co, 4) if halo else 0)
            # the widest phase's tap-batched matmul result (f32)
            + tile_bytes(rows, taps * block_co, 4))


# -- Backward (VJP) kernels: the adjoint on the SAME fused 4D grid -----------

def deconv_dx_pallas_3d(dy: jax.Array, w: jax.Array, *,
                        kernel: Sequence[int], stride: Sequence[int],
                        block_ci: int, block_co: int, dtile: int,
                        interpret: bool,
                        dilation: Sequence[int] | None = None,
                        groups: int = 1,
                        out_dtype=None) -> jax.Array:
    """dx on the uniform grid: one ``pallas_call``, any dy size.

    Deconv's adjoint is a strided convolution: dx[i] = sum_k dy[i*S+k]·w[k]
    (contracted over Cout).  Since PR 3 that strided-conv body is the
    engine's first-class FORWARD convolution (``kernels.conv.kernel.
    conv_pallas_3d``); this wrapper is the channel-role swap that turns it
    back into deconv's dx — the contracted dim is deconv's Cout and the
    produced dim deconv's Cin, so the conv kernel's (block_ci, block_co)
    are this deconv's (block_co, block_ci).

    dy: [N, n_dtiles*dtile*S_d, OH, OW, Co] — the un-cropped cotangent,
    zero-padded on the leading dim to the tile grid (ops.py pads); trailing
    extents are the exact Eq. (1) forward output, so H/W recover statically.
    w: [prod(K), Ci, Co] in the phase-major tap order (the same layout the
    forward consumes — ops.py gathers it once); the conv kernel reads it as
    [prod(K), out, contracted].  Returns [N, n_dtiles*dtile, H, W, Ci];
    rows at or beyond the true input extent are cropped by the caller.
    """
    # Lazy import: kernels.conv's ops pull deconv kernels for THEIR
    # backward, so a module-level import here would be circular.
    from repro.kernels.conv import kernel as _conv_k
    return _conv_k.conv_pallas_3d(
        dy, w, kernel=kernel, stride=stride,
        block_ci=block_co, block_co=block_ci, dtile=dtile,
        dilation=dilation, groups=groups,
        interpret=interpret, out_dtype=out_dtype or dy.dtype)


def _deconv_dw_kernel_body(x_ref, dy_ref, o_ref, acc_ref, xext_ref, *,
                           rows, plane, row_w, margin, dtile, halo, kernel,
                           stride, dilation, n_batch, n_dtiles, out_dtype):
    """One grid step of dw: per-tap [bci, bco] contractions into VMEM.

    dw[k, ci, co] = sum_{n, i} x[n, i, ci] * dy[n, i*S+k, co] — for each tap
    the contraction runs over the whole (batch, spatial) extent, so it
    accumulates across the sequential (N, d-tile) grid dims into an f32 VMEM
    scratch and flushes once at the last step.  In phase terms tap ``m`` of
    phase ``p`` pairs ``x[u - m]`` with ``dy_p[u]``: on the flattened tile
    grid that is ONE row offset into an f32 copy of x (``xext_ref``), whose
    head holds a zero margin and the previous tile's last M_d - 1 rows —
    cross-tile pairs never leave VMEM and iteration stays forward.

    The whole phase is ONE MXU dispatch: its shifted x windows sit side by
    side as a [rows, n_taps*bci] operand and contract against the phase's dy
    slab into every per-tap [bci, bco] block at once — S^d dispatches per
    grid step here too, not K^d.  The scratch is laid out tap-flat in the
    same phase-major order as the weights (contiguous per-phase runs); the
    caller unscrambles.

    x_ref:    [1, 1, rows, bci]             (zero-padded flattened tile)
    dy_ref:   [1, 1, prod(S), rows, bco]    (host-split output phases)
    o_ref:    [prod(K), bci, bco]           (phase-major tap order)
    acc_ref:  VMEM f32 [prod(K)*bci, bco]
    xext_ref: VMEM f32 [margin + (M_d-1)*plane + rows, bci]
    """
    b = pl.program_id(2)
    t = pl.program_id(3)
    bci = xext_ref.shape[-1]
    base = margin + halo * plane
    cdt = mxu_dtype(x_ref.dtype, dy_ref.dtype)

    @pl.when(jnp.logical_and(b == 0, t == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if base:
        @pl.when(t == 0)
        def _zero_head():
            xext_ref[pl.ds(0, base), :] = jnp.zeros((base, bci), jnp.float32)

    xext_ref[pl.ds(base, rows), :] = x_ref[0, 0].astype(jnp.float32)

    off = 0
    for p_idx, _, taps in _phase_taps(kernel, stride, dilation):
        dy = dy_ref[0, 0, p_idx].astype(jnp.float32)    # [rows, bco]
        wins = [xext_ref[pl.ds(base - (m[0] * plane + m[1] * row_w + m[2]),
                               rows), :] for m in taps]
        xs = wins[0] if len(wins) == 1 else jnp.concatenate(wins, axis=1)
        res = jax.lax.dot_general(
            xs, dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=mxu_precision(cdt))           # [n_taps*bci, bco]
        acc_ref[pl.ds(off * bci, len(taps) * bci), :] += res
        off += len(taps)

    if halo:
        # recursive like the forward halo: composes when dtile < M_d - 1
        hp = halo * plane
        xext_ref[pl.ds(margin, hp), :] = \
            xext_ref[pl.ds(margin + dtile * plane, hp), :]

    @pl.when(jnp.logical_and(b == n_batch - 1, t == n_dtiles - 1))
    def _flush():
        for k in range(o_ref.shape[0]):
            o_ref[k] = acc_ref[pl.ds(k * bci, bci), :].astype(out_dtype)


def deconv_dw_pallas_3d(x: jax.Array, dy: jax.Array, *,
                        kernel: Sequence[int], stride: Sequence[int],
                        block_ci: int, block_co: int, dtile: int,
                        interpret: bool,
                        dilation: Sequence[int] | None = None,
                        groups: int = 1,
                        out_dtype=None) -> jax.Array:
    """dw on the uniform grid: one ``pallas_call`` reducing over (N, tiles).

    x: [N, n_dtiles*dtile, H, W, Ci] (leading dim zero-padded to the tile
    grid — padded rows pair only with padded/zero dy rows, contributing
    nothing); dy: [N, n_dtiles*dtile*S_d, OH, OW, Co] un-cropped and padded
    likewise.  Returns dw [prod(K), Ci/G, Co] in PHASE-MAJOR tap order —
    with groups, the ci grid dim spans ONE group's input blocks and the x
    index map routes each co block to its group's slab, so the output IS
    the grouped weight layout.  The caller inverts
    ``phase_major_tap_index`` and crops channel padding per group.
    """
    n, d_pad, h, wdim, ci = x.shape
    co = dy.shape[-1]
    kernel = tuple(kernel)
    stride = tuple(stride)
    dilation = tuple(dilation) if dilation is not None else (1,) * len(kernel)
    out_dtype = out_dtype or x.dtype
    assert d_pad % dtile == 0, (d_pad, dtile)
    n_dt = d_pad // dtile
    assert dy.shape[1] == d_pad * stride[0], (dy.shape, d_pad, stride)
    assert ci % groups == 0 and co % groups == 0, (ci, co, groups)
    cig = ci // groups
    assert cig % block_ci == 0 and co % block_co == 0, (ci, co,
                                                        block_ci, block_co)
    n_ci, n_co = cig // block_ci, co // block_co
    assert n_co % groups == 0, (n_co, groups)
    nco_g = n_co // groups
    m_max = _phase_geometry(kernel, stride, dilation)
    halo = halo_depth(kernel, stride, dilation)
    grid_hw = flat_grid((h, wdim), m_max[1:])
    plane = grid_hw[0] * grid_hw[1]
    rows = dtile * plane
    margin = (m_max[1] - 1) * grid_hw[1] + m_max[2] - 1
    n_taps = math.prod(kernel)
    n_phases = math.prod(stride)

    body = functools.partial(
        _deconv_dw_kernel_body, rows=rows, plane=plane, row_w=grid_hw[1],
        margin=margin, dtile=dtile, halo=halo, kernel=kernel, stride=stride,
        dilation=dilation, n_batch=n, n_dtiles=n_dt, out_dtype=out_dtype)
    scratch = [pltpu.VMEM((n_taps * block_ci, block_co), jnp.float32),
               pltpu.VMEM((margin + halo * plane + rows, block_ci),
                          jnp.float32)]
    step = vmem_bytes_dw((d_pad, h, wdim), kernel, stride, block_ci,
                         block_co, jnp.dtype(x.dtype).itemsize, dtile=dtile,
                         dilation=dilation)
    return pl.pallas_call(
        body,
        grid=(n_ci, n_co, n, n_dt),
        in_specs=[
            pl.BlockSpec((1, 1, rows, block_ci),
                         lambda ic, oc, b, t: (b, t, 0,
                                               (oc // nco_g) * n_ci + ic)),
            pl.BlockSpec((1, 1, n_phases, rows, block_co),
                         lambda ic, oc, b, t: (b, t, 0, 0, oc)),
        ],
        out_specs=pl.BlockSpec((n_taps, block_ci, block_co),
                               lambda ic, oc, b, t: (0, ic, oc)),
        out_shape=jax.ShapeDtypeStruct((n_taps, cig, co), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=compiler_params(
            step, ("parallel", "parallel", "arbitrary", "arbitrary")),
    )(to_tiles(x, n_dt, grid_hw), to_phases(dy, stride, n_dt, grid_hw))


def vmem_bytes_dx(in_spatial, kernel, stride, block_ci, block_co,
                  in_dtype_bytes: int = 2, dtile: int | None = None,
                  dilation=None) -> int:
    """Static per-grid-step VMEM footprint of the dx VJP kernel.

    dx is the engine's strided convolution with the channel roles swapped
    (contract Cout, produce Cin), so this is exactly the conv kernel's
    model with ``in_spatial`` — deconv's input = the conv's output — as
    the tiled extent and (block_co, block_ci) as its (block_ci, block_co).
    """
    from repro.kernels.conv import kernel as _conv_k  # lazy: avoids a cycle
    return _conv_k.vmem_bytes(in_spatial, kernel, stride,
                              block_co, block_ci, in_dtype_bytes,
                              dtile=dtile, dilation=dilation)


def vmem_bytes_dw(in_spatial, kernel, stride, block_ci, block_co,
                  in_dtype_bytes: int = 2, dtile: int | None = None,
                  dilation=None) -> int:
    """Static per-grid-step VMEM footprint of the dw VJP kernel.

    Models, in Mosaic's tiled layout, the double-buffered x tile, dy phase
    slab and dw output block, the f32 dw scratch and the f32 x copy with
    its margin and carry, and the widest phase's window batch, f32 dy and
    contraction result.
    """
    in_spatial, kernel, stride, dilation = lift_geometry3(
        in_spatial, kernel, stride, dilation)
    m_max = _phase_geometry(kernel, stride, dilation)
    halo = m_max[0] - 1
    if dtile is None:
        dtile = in_spatial[0] + halo
    grid_hw = flat_grid(tuple(in_spatial[1:]), m_max[1:])
    plane = grid_hw[0] * grid_hw[1]
    rows = dtile * plane
    margin = (m_max[1] - 1) * grid_hw[1] + m_max[2] - 1
    k_elems = math.prod(kernel)
    taps = math.prod(m_max)
    return (2 * tile_bytes(rows, block_ci, in_dtype_bytes)
            + 2 * math.prod(stride) * tile_bytes(rows, block_co,
                                                 in_dtype_bytes)
            + 2 * k_elems * tile_bytes(block_ci, block_co, in_dtype_bytes)
            + tile_bytes(k_elems * block_ci, block_co, 4)
            + tile_bytes(margin + (halo + dtile) * plane, block_ci, 4)
            # the widest phase: x windows, f32 dy and the contraction
            + tile_bytes(rows, taps * block_ci, 4)
            + tile_bytes(rows, block_co, 4)
            + tile_bytes(taps * block_ci, block_co, 4))


def vmem_bytes_bwd(in_spatial, kernel, stride, block_ci, block_co,
                   in_dtype_bytes: int = 2, dtile: int | None = None,
                   dilation=None) -> int:
    """Static per-grid-step VMEM footprint of the two VJP kernels (max).

    The planner budgets ``max(forward, dx, dw)`` when asked to plan for
    training; see ``vmem_bytes_dx`` / ``vmem_bytes_dw``.
    """
    return max(vmem_bytes_dx(in_spatial, kernel, stride, block_ci, block_co,
                             in_dtype_bytes, dtile=dtile, dilation=dilation),
               vmem_bytes_dw(in_spatial, kernel, stride, block_ci, block_co,
                             in_dtype_bytes, dtile=dtile, dilation=dilation))
