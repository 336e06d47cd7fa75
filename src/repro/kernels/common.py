"""Shared polyphase geometry for the uniform conv/deconv Pallas engine.

Both kernel families — the deconv forward (``kernels.deconv.kernel``) and
the first-class strided convolution (``kernels.conv.kernel``) — run on the
same fused 4D grid and share one tap bookkeeping: a stride-S deconv scatters
each input activation through the S^d output phases, and its adjoint (a
stride-S convolution) gathers the same taps back from the S^d input phases.
The static geometry of that correspondence lives here so the two subsystems
cannot drift:

  * ``phase_geometry`` — taps per phase per dim, ``M = ceil(K/S)``,
  * ``halo_depth`` — leading-dim rows adjacent grid tiles exchange (the
    paper's FIFO-D carry depth),
  * ``phase_taps`` — the static (phase, valid taps) table; summed over
    phases the taps number exactly K^d (the IOM valid-MAC count),
  * ``phase_major_tap_index`` — the weight gather that lands each phase's
    taps contiguously, feeding ONE wide MXU matmul per phase.
"""

from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.functional import _canon

# Scoped-VMEM limit handed to Mosaic: the modeled working set plus headroom
# for compiler temporaries, at least 32 MiB, and capped under the 128 MiB
# of VMEM a v5e core has.
VMEM_LIMIT_FLOOR = 32 * 1024 * 1024
VMEM_LIMIT_CAP = 100 * 1024 * 1024


def compiler_params(step_bytes: int, semantics) -> pltpu.CompilerParams:
    """Mosaic params for one engine kernel: grid semantics plus a scoped
    VMEM limit derived from the same byte model the planner budgets."""
    limit = min(VMEM_LIMIT_CAP,
                max(VMEM_LIMIT_FLOOR, step_bytes * 3 // 2 + (4 << 20)))
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics),
                                vmem_limit_bytes=int(limit))


def canon_dilation(dilation, rank):
    """None / int / seq -> rank-length tuple of per-dim dilation factors."""
    if dilation is None:
        return (1,) * rank
    return tuple(_canon(dilation, rank))


def effective_kernel(kernel, dilation=None):
    """Dilated footprint per dim: K_eff = (K - 1) * dil + 1."""
    dil = canon_dilation(dilation, len(kernel))
    return tuple((k - 1) * d + 1 for k, d in zip(kernel, dil))


def _dim_tap_table(k, s, d):
    """Per-dim polyphase map: phase p -> sorted [(m, k_idx), ...].

    Kernel element ``k_idx`` of a dilation-``d`` kernel sits at effective
    offset ``e = k_idx * d``; under stride ``s`` it lands in phase
    ``e % s`` as tap ``m = e // s``.  Distinct elements get distinct
    (p, m) pairs, and under dilation some phases may receive no taps at
    all (structural zeros).
    """
    table = {}
    for ki in range(k):
        e = ki * d
        table.setdefault(e % s, []).append((e // s, ki))
    return table


def phase_geometry(kernel, stride, dilation=None):
    """Static geometry: M_max (taps per phase per dim) and acc lengths.

    With dilation the deepest tap of any phase is ``((K-1)*dil) // S``; at
    dil=1 this reduces to the familiar ``ceil(K/S)``.
    """
    dil = canon_dilation(dilation, len(kernel))
    return tuple(((k - 1) * d) // s + 1
                 for k, s, d in zip(kernel, stride, dil))


def halo_depth(kernel, stride, dilation=None) -> int:
    """Phase rows adjacent leading-dim tiles exchange (FIFO-D carry depth)."""
    return phase_geometry(kernel, stride, dilation)[0] - 1


def phase_taps(kernel, stride, dilation=None):
    """Static (phase_index, phase, valid taps) triples; empty phases skipped.

    A tap ``m`` of phase ``p`` touches the kernel element whose *effective*
    offset is ``e = m*S + p``; under dilation only offsets divisible by the
    per-dim factor carry a weight, so each phase's tap list is the cross
    product of the per-dim polyphase tables.  Summed over phases the
    surviving taps number exactly K^d — the IOM valid-MAC count.
    """
    dil = canon_dilation(dilation, len(kernel))
    tables = [_dim_tap_table(k, s, d)
              for k, s, d in zip(kernel, stride, dil)]
    out = []
    for p_idx, p in enumerate(itertools.product(*(range(s) for s in stride))):
        dim_taps = [t.get(pj) for t, pj in zip(tables, p)]
        if any(dt is None for dt in dim_taps):
            continue  # structural-zero phase (S > K, or dilation gaps)
        taps = [tuple(m for m, _ in combo)
                for combo in itertools.product(*dim_taps)]
        out.append((p_idx, p, taps))
    return out


def phase_major_tap_index(kernel, stride, dilation=None):
    """Flat kernel-element indices ordered phase-major (the weight layout).

    The caller gathers ``w.reshape(prod(K), ci, co)[index]`` so each phase's
    valid taps sit contiguously: the kernel bodies then feed a whole phase
    to the MXU with ONE static slice — no per-tap loads, no zero-padded
    Kpad tail.  Total length is exactly prod(K): every kernel element
    belongs to exactly one phase.  Must stay in lock-step with the tap
    order ``phase_taps`` emits.
    """
    dil = canon_dilation(dilation, len(kernel))
    tables = [_dim_tap_table(k, s, d)
              for k, s, d in zip(kernel, stride, dil)]
    idx = []
    for p in itertools.product(*(range(s) for s in stride)):
        dim_taps = [t.get(pj) for t, pj in zip(tables, p)]
        if any(dt is None for dt in dim_taps):
            continue
        for combo in itertools.product(*dim_taps):
            flat = 0
            for (_, kj), kk in zip(combo, kernel):
                flat = flat * kk + kj
            idx.append(flat)
    assert len(idx) == math.prod(kernel)
    return idx


def phase_major_inverse(kernel, stride, dilation=None):
    """Inverse of ``phase_major_tap_index`` — unscrambles dw outputs.

    The dw kernel emits taps phase-major; indexing its output with this
    permutation restores kernel-element order (both ops layers' backwards
    use it).
    """
    perm = phase_major_tap_index(kernel, stride, dilation)
    inv = [0] * len(perm)
    for pos, j in enumerate(perm):
        inv[j] = pos
    return inv


# -- Fused epilogue (bias + activation inside the kernel flush) --------------

ACTIVATIONS = ("none", "relu", "leaky_relu", "tanh")


def apply_epilogue(y, bias, activation, alpha=0.2, scale=None):
    """Scale + bias-add + activation, applied to a completed accumulator.

    Runs inside the kernel flush (values, not refs) and on the host for the
    XLA-flavoured engines — one definition so the two paths cannot drift.
    ``scale`` is the per-output-channel dequant factor of the quantized
    paths; it multiplies the raw accumulator FIRST (scale → bias →
    activation) so the bias stays in real units.  Both ``scale`` and
    ``bias`` broadcast over everything but the trailing channel dim.
    """
    if scale is not None:
        y = y * scale.reshape((1,) * (y.ndim - 1) + (-1,)).astype(y.dtype)
    if bias is not None:
        y = y + bias.reshape((1,) * (y.ndim - 1) + (-1,)).astype(y.dtype)
    if activation == "relu":
        y = jnp.maximum(y, 0)
    elif activation == "leaky_relu":
        y = jnp.where(y > 0, y, jnp.asarray(alpha, y.dtype) * y)
    elif activation == "tanh":
        y = jnp.tanh(y)
    elif activation != "none":
        raise ValueError(f"unknown activation {activation!r}")
    return y


def activation_grad_from_output(y, activation, alpha=0.2):
    """d(act)/d(pre-activation) computed from the *output* y = act(pre).

    All supported activations are invertible enough for this: relu and
    leaky_relu keep the sign of the pre-activation, tanh' = 1 - y^2.
    Returns None for the identity (no rescaling needed).
    """
    if activation == "relu":
        return (y > 0).astype(y.dtype)
    if activation == "leaky_relu":
        return jnp.where(y > 0, jnp.ones_like(y), jnp.full_like(y, alpha))
    if activation == "tanh":
        return (1 - y * y).astype(y.dtype)
    return None


def operand_plan_bytes(dtype) -> int:
    """Planner width of an operand dtype.

    Quantized (integer) operands count their true width; float operands
    keep the NOMINAL bf16 width the byte model has always assumed, so
    every pre-existing f32/bf16 plan (and persisted tuned-plan cache
    entry) is unchanged.
    """
    dt = jnp.dtype(dtype)
    return dt.itemsize if jnp.issubdtype(dt, jnp.integer) else 2


def default_interpret() -> bool:
    """Pallas interpret-mode default: emulate everywhere but real TPUs."""
    return jax.default_backend() != "tpu"


# -- Flattened tile layout (what the kernel bodies see) ----------------------
#
# Mosaic lowers 2-D [rows, lanes] values well and higher-rank reshapes,
# strided value slices and batched 3-D contractions poorly.  So every kernel
# block is a 2-D slab: one leading-dim tile of ``dtile`` rows with its two
# trailing spatial dims zero-padded onto a (Lh, Lw) grid and flattened,
# ``[dtile*Lh*Lw, C]``.  On that grid a spatial shift by tap ``m`` is ONE
# static row offset ``m_d*Lh*Lw + m_h*Lw + m_w``: the grid is wide enough
# (``L = extent + M - 1``) that a shifted valid element never wraps onto
# another valid element, so wrapped terms only ever touch padding positions,
# which the host crops.  Stride phases are split (inputs) and interleaved
# (outputs) on the host, so no kernel strides or transposes a value.

def round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def tile_bytes(rows: int, cols: int, dtype_bytes: int) -> int:
    """VMEM bytes of a [rows, cols] slab in Mosaic's tiled layout: lanes
    pad to 128, sublanes to 8 rows of 32-bit words (16 bf16, 32 int8)."""
    sub = 8 * max(1, 4 // dtype_bytes)
    return round_up(max(rows, 1), sub) * round_up(max(cols, 1), 128) \
        * dtype_bytes


def lift_geometry3(spatial, kernel, stride, dilation=None):
    """Lift a rank-1/2 (spatial, kernel, stride, dilation) geometry onto
    the canonical rank-3 layout the kernels run on (``lift_3d``'s rule)."""
    rank = len(tuple(spatial))
    dil = canon_dilation(dilation, rank)
    return tuple(lift_tuple3(v, rank) for v in (spatial, kernel, stride, dil))


def flat_grid(extent, m_max):
    """(Lh, Lw): the trailing-dim grid a kernel flattens its slabs onto."""
    return tuple(e + m - 1 for e, m in zip(extent, m_max))


def fit_axis(a, axis: int, size: int):
    """Zero-pad or crop ``axis`` of ``a`` to exactly ``size``."""
    cur = a.shape[axis]
    if cur > size:
        return jax.lax.slice_in_dim(a, 0, size, axis=axis)
    if cur < size:
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, size - cur)
        a = jnp.pad(a, widths)
    return a


def to_tiles(a, n_dt: int, grid):
    """[N, n_dt*dtile, h, w, C] -> [N, n_dt, dtile*Lh*Lw, C] on ``grid``."""
    a = fit_axis(fit_axis(a, 2, grid[0]), 3, grid[1])
    n, d, c = a.shape[0], a.shape[1], a.shape[-1]
    return a.reshape(n, n_dt, (d // n_dt) * grid[0] * grid[1], c)


def from_tiles(a, grid):
    """Inverse of ``to_tiles``: [N, n_dt, R, C] -> [N, n_dt*R/(Lh*Lw), Lh,
    Lw, C]."""
    n, n_dt, r, c = a.shape
    return a.reshape(n, n_dt * r // (grid[0] * grid[1]), grid[0], grid[1], c)


def to_phases(a, stride, n_dt: int, grid):
    """Split strided rows into phases: [N, n_dt*dtile*S_d, H', W', C] ->
    [N, n_dt, prod(S), dtile*Lh*Lw, C] with phase ``p`` (row-major over
    the per-dim phases, the ``phase_taps`` order) holding ``a[u*S + p]``
    on the (dtile, Lh, Lw) grid of each tile."""
    sd, sh, sw = stride
    a = fit_axis(fit_axis(a, 2, grid[0] * sh), 3, grid[1] * sw)
    n, d, c = a.shape[0], a.shape[1], a.shape[-1]
    dtile = d // (n_dt * sd)
    a = a.reshape(n, n_dt, dtile, sd, grid[0], sh, grid[1], sw, c)
    a = a.transpose(0, 1, 3, 5, 7, 2, 4, 6, 8)
    return a.reshape(n, n_dt, sd * sh * sw, dtile * grid[0] * grid[1], c)


def from_phases(a, stride, grid):
    """Interleave phases: the inverse of ``to_phases`` —
    [N, n_dt, prod(S), dtile*Lh*Lw, C] -> [N, n_dt*dtile*S_d, Lh*S_h,
    Lw*S_w, C] with ``out[q*S + p] = a[p, q]``."""
    sd, sh, sw = stride
    n, n_dt, _, r, c = a.shape
    dtile = r // (grid[0] * grid[1])
    a = a.reshape(n, n_dt, sd, sh, sw, dtile, grid[0], grid[1], c)
    a = a.transpose(0, 1, 5, 2, 6, 3, 7, 4, 8)
    return a.reshape(n, n_dt * dtile * sd, grid[0] * sh, grid[1] * sw, c)


def mxu_dtype(*dtypes):
    """Common operand dtype of an in-kernel matmul: integer operands widen
    to f32 (|q| <= 127 is exact), floats promote to the wider one."""
    if any(jnp.issubdtype(d, jnp.integer) for d in dtypes):
        return jnp.dtype(jnp.float32)
    return jnp.dtype(jnp.result_type(*dtypes))


def mxu_precision(dtype):
    """f32 operands contract at full f32 precision (the engine's f32
    contract); narrower floats take the MXU's native pass."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else None)


def phase_weight_slab(w_ref, off: int, n: int, dtype):
    """The phase's ``n`` contiguous taps of a [prod(K), a, b] weight block
    side by side as ONE [a, n*b] operand (a lane concatenation), so the
    whole phase is a single MXU matmul."""
    ws = [w_ref[off + t].astype(dtype) for t in range(n)]
    return ws[0] if n == 1 else jnp.concatenate(ws, axis=1)


# -- Host-side canonicalisation shared by both ops layers --------------------

def pad_axis_to(x, axis, mult):
    """Zero-pad ``axis`` of ``x`` up to the next multiple of ``mult``."""
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pad_group_axis(x, axis, groups, mult):
    """Pad each of ``groups`` equal chunks along ``axis`` to a multiple.

    The grouped kernels block the channel grid *per group*, so padding must
    land at the tail of every group chunk — a flat ``pad_axis_to`` would
    misalign every group after the first.  ``groups == 1`` degenerates to
    ``pad_axis_to``.
    """
    axis = axis % x.ndim
    per = x.shape[axis] // groups
    pad = (-per) % mult
    if pad == 0:
        return x
    shape = x.shape[:axis] + (groups, per) + x.shape[axis + 1:]
    widths = [(0, 0)] * (x.ndim + 1)
    widths[axis + 1] = (0, pad)
    xg = jnp.pad(x.reshape(shape), widths)
    return xg.reshape(x.shape[:axis] + (groups * (per + pad),)
                      + x.shape[axis + 1:])


def crop_group_axis(x, axis, groups, per):
    """Inverse of ``pad_group_axis``: keep the first ``per`` of each chunk."""
    axis = axis % x.ndim
    padded = x.shape[axis] // groups
    if padded == per:
        return x
    shape = x.shape[:axis] + (groups, padded) + x.shape[axis + 1:]
    xg = x.reshape(shape)
    sl = [slice(None)] * xg.ndim
    sl[axis + 1] = slice(0, per)
    xg = xg[tuple(sl)]
    return xg.reshape(x.shape[:axis] + (groups * per,) + x.shape[axis + 1:])


def phase_major_weights(w3, kernel3, stride3, dilation3=None):
    """[K..., a, b] -> [prod(K), a, b] in phase-major tap order.

    Each phase's valid taps land contiguously, so the kernel bodies slice a
    whole phase for their tap-batched matmul — see
    ``phase_major_tap_index``.  The gather is a static permutation, fused by
    XLA; the trailing two dims are whatever channel pair the caller uses
    ([ci, co] for deconv, [co, ci] for the forward conv).
    """
    idx = phase_major_tap_index(kernel3, stride3, dilation3)
    flat = w3.reshape(-1, *w3.shape[3:])
    return flat[jnp.asarray(idx)]


def lift_tuple3(vals, rank, fill=1):
    """Lift a rank-length per-dim tuple to rank 3 the way ``lift_3d`` lifts
    activations: rank 2 puts the singleton in the MIDDLE, rank 1 leads with
    two.  Used for dilation (and any future per-dim knob)."""
    vals = tuple(vals)
    if rank == 3:
        return vals
    if rank == 2:
        return (vals[0], fill, vals[1])
    return (fill, fill, vals[0])


def lift_3d(x, w, stride):
    """Canonicalise rank-1/2 inputs to rank-3; returns squeeze axes.

    Rank 2 lifts [N, H, W, C] -> [N, H, 1, W, C] (singleton in the MIDDLE):
    the large image dim lands on the leading axis — the one the fused grid
    tiles — while W stays innermost on the lanes.  Rank 1 lifts to
    [N, 1, 1, W, C].  Shared by the deconv and conv ops layers (the weight
    layout [*K, c_a, c_b] lifts identically for either channel order).
    """
    rank = x.ndim - 2
    stride = _canon(stride, rank)
    if rank == 3:
        return x, w, tuple(stride), ()
    if rank == 2:
        x3 = x.reshape(x.shape[0], x.shape[1], 1, x.shape[2], x.shape[3])
        w3 = w.reshape(w.shape[0], 1, w.shape[1], w.shape[2], w.shape[3])
        return x3, w3, (stride[0], 1, stride[1]), (2,)
    x3 = x.reshape(x.shape[0], 1, 1, x.shape[1], x.shape[2])
    w3 = w.reshape(1, 1, *w.shape)
    return x3, w3, (1, 1, stride[0]), (1, 2)
