"""Pallas kernel vs pure-jnp oracle: shape/dtype sweeps, gradients, blocking
and the fused multi-tile grid (interpret mode on CPU)."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.jaxpr_utils import count_prims as _count_prims
from repro.core.jaxpr_utils import pallas_eqns as _pallas_eqns
from repro.core.tiling import plan_uniform_tiles
from repro.kernels.deconv import deconv, deconv_reference
from repro.kernels.deconv import ops as deconv_ops
from repro.kernels.deconv.kernel import vmem_bytes

SHAPES = [
    (2, (4, 4), (3, 3), (2, 2), 1, 7, 5),
    (1, (8, 8), (3, 3), (2, 2), 0, 16, 8),
    (2, (3, 4, 3), (3, 3, 3), (2, 2, 2), 1, 5, 3),
    (1, (4, 4, 4), (3, 3, 3), (2, 2, 2), 0, 8, 8),
    (2, (5, 3), (2, 3), (3, 2), 0, 3, 2),
    (1, (6,), (3,), (2,), 0, 4, 4),
    (1, (2, 3, 4), (4, 3, 2), (2, 3, 1), 0, 3, 2),
    (1, (4, 4), (5, 5), (2, 2), 2, 4, 4),
    (3, (7, 5), (3, 3), (2, 2), 1, 6, 9),   # non-pow2 channels -> padding
]


@pytest.mark.parametrize("n,I,K,S,P,ci,co", SHAPES)
def test_pallas_matches_oracle_f32(rng, n, I, K, S, P, ci, co):
    x = jnp.asarray(rng.randn(n, *I, ci), jnp.float32)
    w = jnp.asarray(rng.randn(*K, ci, co), jnp.float32)
    ref = deconv_reference(x, w, S, P)
    got = deconv(x, w, S, P)
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 3e-2)])
def test_pallas_dtypes(rng, dtype, tol):
    x = jnp.asarray(rng.randn(2, 4, 4, 8), dtype)
    w = jnp.asarray(rng.randn(3, 3, 8, 8) * 0.2, dtype)
    ref = np.asarray(deconv_reference(x.astype(jnp.float32),
                                      w.astype(jnp.float32), 2, 1))
    got = np.asarray(deconv(x, w, 2, 1)).astype(np.float32)
    np.testing.assert_allclose(got, ref, rtol=tol, atol=tol * 3)


def test_pallas_gradients_match_reference(rng):
    x = jnp.asarray(rng.randn(2, 4, 4, 3), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 3, 4), jnp.float32)

    def f_pallas(x, w):
        return jnp.sum(jnp.sin(deconv(x, w, 2, 1)))

    def f_ref(x, w):
        return jnp.sum(jnp.sin(deconv_reference(x, w, 2, 1)))

    gp = jax.grad(f_pallas, (0, 1))(x, w)
    gr = jax.grad(f_ref, (0, 1))(x, w)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_fused_multitile_3d(rng):
    """A tiny VMEM budget forces the multi-tile 4D grid on a 3D input; the
    in-kernel halo overlap-add must reproduce the oracle exactly."""
    x = jnp.asarray(rng.randn(1, 16, 8, 8, 4), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 3, 4, 4), jnp.float32)
    plan = plan_uniform_tiles((16, 8, 8), (3, 3, 3), (2, 2, 2), 4, 4,
                             vmem_budget=64 * 1024)
    assert plan.n_dtiles > 1
    ref = deconv_reference(x, w, 2, 1)
    got = deconv(x, w, 2, 1, max_tile_bytes=64 * 1024)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_fused_multitile_2d(rng):
    """2D inputs lift as [N, H, 1, W, C], so the big image dim is the one
    the grid tiles — the multi-tile path engages for 2D too."""
    x = jnp.asarray(rng.randn(1, 32, 8, 3), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 3, 5), jnp.float32)
    plan = plan_uniform_tiles((32, 1, 8), (3, 1, 3), (2, 1, 2), 3, 5,
                             vmem_budget=16 * 1024)
    assert plan.n_dtiles > 1
    got = deconv(x, w, 2, 0, max_tile_bytes=16 * 1024)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(deconv_reference(x, w, 2, 0)),
                               rtol=1e-4, atol=1e-4)


def test_fused_multitile_stride_gt_kernel(rng):
    """S > K on the tiled dim: no halo rows at all (M_d == 1); tiles own
    disjoint output slabs with structural zero gaps between phases."""
    x = jnp.asarray(rng.randn(1, 12, 6, 2), jnp.float32)
    w = jnp.asarray(rng.randn(2, 2, 2, 3), jnp.float32)
    got = deconv(x, w, 3, 0, max_tile_bytes=8 * 1024)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(deconv_reference(x, w, 3, 0)),
                               rtol=1e-4, atol=1e-4)


def test_fused_multitile_deep_halo_nondivisible(rng):
    """K_d much larger than S_d * dtile: the carry spans several tiles and
    must compose recursively; the leading dim (13) does not divide the tile
    (2), so the zero-padded tail tiles must contribute nothing."""
    x = jnp.asarray(rng.randn(1, 13, 4, 2), jnp.float32)
    w = jnp.asarray(rng.randn(7, 3, 2, 2), jnp.float32)
    x3, w3, stride3, squeeze = deconv_ops._lift_3d(x, w, (1, 2))
    got = deconv_ops._core_call(x3, w3, stride3, w3.shape[:3], 8, 8, True,
                                dtile=2, n_dtiles=10)
    got = jnp.squeeze(got, axis=squeeze)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(deconv_reference(x, w, (1, 2), 0)),
        rtol=1e-4, atol=1e-4)


def test_fused_multitile_gradients(rng):
    """Forward through the multi-tile grid + custom-VJP gradients match the
    oracle for both 2D and 3D cases."""
    cases = [
        (rng.randn(1, 12, 6, 2), rng.randn(3, 3, 2, 3), (2, 2), 32 * 1024),
        (rng.randn(1, 10, 4, 4, 2), rng.randn(3, 3, 3, 2, 2), (2, 2, 2),
         48 * 1024),
    ]
    for xa, wa, stride, budget in cases:
        x = jnp.asarray(xa, jnp.float32)
        w = jnp.asarray(wa, jnp.float32)

        def f_pallas(x, w):
            return jnp.sum(jnp.sin(deconv(x, w, stride, 1,
                                          max_tile_bytes=budget)))

        def f_ref(x, w):
            return jnp.sum(jnp.sin(deconv_reference(x, w, stride, 1)))

        gp = jax.grad(f_pallas, (0, 1))(x, w)
        gr = jax.grad(f_ref, (0, 1))(x, w)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)




def test_split_is_single_pallas_call(rng):
    """The acceptance criterion made structural: even when the planner
    splits, the traced forward contains exactly ONE pallas_call and no
    dynamic_update_slice stitching."""
    x = jnp.asarray(rng.randn(1, 16, 8, 8, 4), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 3, 4, 4), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda x, w: deconv(x, w, 2, 1, max_tile_bytes=64 * 1024))(x, w)
    counts = _count_prims(jaxpr.jaxpr, {})
    assert counts.get("pallas_call") == 1, counts
    assert "dynamic_update_slice" not in counts, counts


def test_planner_respects_budget_and_explicit_blocks():
    plan = plan_uniform_tiles((64, 16, 16), (3, 3, 3), (2, 2, 2), 256, 256,
                             vmem_budget=1 << 20)
    assert plan.step_vmem_bytes <= 1 << 20 or (
        plan.dtile == 1 and plan.block_ci == 8 and plan.block_co == 8)
    assert plan.n_dtiles * plan.dtile >= 64 + 1   # covers data + halo slack
    pinned = plan_uniform_tiles((64, 16, 16), (3, 3, 3), (2, 2, 2), 256, 256,
                               vmem_budget=1 << 20, block_ci=32, block_co=16)
    assert (pinned.block_ci, pinned.block_co) == (32, 16)


def test_block_choice_respects_vmem():
    """The old choose_blocks behaviour (channels-only shrink) is the
    planner's allow_split=False mode — one entry point, one VMEM model."""
    plan = plan_uniform_tiles((4, 4, 4), (3, 3, 3), (2, 2, 2), 256, 256,
                              vmem_budget=2 << 20, allow_split=False)
    bci, bco = plan.block_ci, plan.block_co
    assert plan.n_dtiles == 1
    assert (bci, bco) != (128, 128)          # the channels had to shrink
    assert vmem_bytes((4, 4, 4), (3, 3, 3), (2, 2, 2), bci, bco) <= 2 << 20
    assert bci >= 8 and bco >= 8


def test_explicit_blocks(rng):
    x = jnp.asarray(rng.randn(1, 8, 8, 32), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 32, 16), jnp.float32)
    ref = deconv_reference(x, w, 2, 0)
    for bci, bco in [(8, 8), (16, 16), (32, 8)]:
        got = deconv(x, w, 2, 0, block_ci=bci, block_co=bco)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


VJP_CASES = [
    # (in_spatial, K, S, P, ci, co, max_tile_bytes)
    ((5, 6), (3, 3), (2, 2), 1, 3, 4, None),          # random 2D
    ((3, 4, 5), (3, 3, 3), (2, 2, 2), 0, 2, 3, None),  # random 3D
    ((14, 5), (3, 3), (2, 2), 0, 2, 2, 16 * 1024),    # forced multi-tile 2D
    ((12, 4, 4), (3, 3, 3), (2, 2, 2), 1, 2, 2, 48 * 1024),  # forced 3D
    ((8, 5), (2, 2), (3, 3), 0, 2, 3, None),          # stride > kernel
    ((8, 4, 4), (7, 3, 3), (2, 2, 2), 1, 2, 3, 24 * 1024),  # deep halo:
    # ceil(K_d/S_d)-1 > dtile, so both backward carries compose recursively
]


@pytest.mark.parametrize("I,K,S,P,ci,co,budget", VJP_CASES)
def test_vjp_matches_conv_transpose_autodiff(rng, I, K, S, P, ci, co,
                                             budget):
    """dx/dw parity against ``jax.lax.conv_transpose`` autodiff (the
    spatially flipped kernel matches our correlation convention; padding is
    a crop applied on top).  Includes a forced multi-tile plan and
    stride > kernel — conv_transpose's VALID extent differs there, so that
    case compares against the pure-jnp oracle instead."""
    rank = len(I)
    x = jnp.asarray(rng.randn(2, *I, ci), jnp.float32)
    w = jnp.asarray(rng.randn(*K, ci, co), jnp.float32)
    kw = dict(max_tile_bytes=budget) if budget else {}

    def f_pallas(x, w):
        return jnp.sum(jnp.sin(deconv(x, w, S, P, **kw)))

    if any(s > k for s, k in zip(S, K)):
        def f_ref(x, w):
            return jnp.sum(jnp.sin(deconv_reference(x, w, S, P)))
    else:
        dn = ("N" + "DHW"[-rank:] + "C", "DHW"[-rank:] + "IO",
              "N" + "DHW"[-rank:] + "C")

        def f_ref(x, w):
            y = jax.lax.conv_transpose(x, jnp.flip(w, tuple(range(rank))),
                                       S, "VALID", dimension_numbers=dn)
            if P:
                y = y[(slice(None),)
                      + tuple(slice(P, d - P) for d in y.shape[1:-1])
                      + (slice(None),)]
            return jnp.sum(jnp.sin(y))

    gp = jax.grad(f_pallas, (0, 1))(x, w)
    gr = jax.grad(f_ref, (0, 1))(x, w)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_backward_is_pallas(rng):
    """The acceptance criterion made structural: the traced backward is
    served by ``pallas_call``s (forward + dx + dw), with NO dot_general /
    einsum running outside the accelerator kernels."""
    x = jnp.asarray(rng.randn(1, 12, 4, 4, 2), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 3, 2, 2), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, w: jnp.sum(deconv(x, w, 2, 1, max_tile_bytes=48 * 1024)),
        (0, 1)))(x, w)
    counts = _count_prims(jaxpr.jaxpr, {}, into_pallas=False)
    assert counts.get("pallas_call") == 3, counts   # fwd + dx + dw
    assert "dot_general" not in counts, counts      # no XLA einsum fallback
    assert "conv_general_dilated" not in counts, counts


@pytest.mark.parametrize("rank,K,S", [(3, (3, 3, 3), (2, 2, 2)),
                                      (2, (5, 5), (2, 2))])
def test_forward_matmuls_are_tap_batched(rng, rank, K, S):
    """Per-phase tap batching: the forward kernel body issues S^d wide MXU
    matmuls per grid step, not K^d small ones (27 -> 8 for 3³/s2, 25 -> 4
    for 5²/s2)."""
    I = (4,) * rank
    x = jnp.asarray(rng.randn(1, *I, 4), jnp.float32)
    w = jnp.asarray(rng.randn(*K, 4, 4), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x, w: deconv(x, w, S, 0))(x, w)
    calls = _pallas_eqns(jaxpr.jaxpr, [])
    assert len(calls) == 1, len(calls)
    dots = _count_prims(calls[0].params["jaxpr"], {}).get("dot_general", 0)
    assert dots == math.prod(S), (dots, math.prod(S), math.prod(K))
    assert dots < math.prod(K)


def test_asymmetric_padding_matches_slice(rng):
    """(lo, hi) padding pairs — the DeconvLayer.crop (0, 1) convention —
    crop inside the op exactly like the old post-hoc slicing, for the
    Pallas op AND every XLA-lowered method, gradients included."""
    from repro.core import deconv_nd

    x = jnp.asarray(rng.randn(2, 5, 6, 3), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 3, 4), jnp.float32)
    full = deconv_reference(x, w, 2, 0)
    for pads, sl in [
        (((0, 1), (0, 1)), (slice(0, -1), slice(0, -1))),
        (((1, 0), (0, 2)), (slice(1, None), slice(0, -2))),
        ((1, (0, 1)), (slice(1, -1), slice(0, -1))),     # mixed scalar/pair
    ]:
        ref = full[(slice(None), *sl, slice(None))]
        got = deconv(x, w, 2, pads)
        assert got.shape == ref.shape, (pads, got.shape, ref.shape)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        for m in ("oom", "xla", "iom", "iom_phase"):
            np.testing.assert_allclose(
                np.asarray(deconv_nd(x, w, 2, pads, method=m)),
                np.asarray(ref), rtol=1e-4, atol=1e-4, err_msg=m)

    pads = ((0, 1), (0, 1))
    gp = jax.grad(lambda x, w: jnp.sum(jnp.sin(deconv(x, w, 2, pads))),
                  (0, 1))(x, w)
    gr = jax.grad(
        lambda x, w: jnp.sum(jnp.sin(
            deconv_reference(x, w, 2, 0)[:, :-1, :-1])), (0, 1))(x, w)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_preferred_element_type_honored(rng):
    """``preferred_element_type`` is no longer silently swallowed: bf16
    inputs emit f32 straight from the f32 in-kernel accumulator (no second
    rounding), and the VJP still returns input-dtype cotangents."""
    x = jnp.asarray(rng.randn(1, 4, 4, 4), jnp.bfloat16)
    w = jnp.asarray(rng.randn(3, 3, 4, 4) * 0.2, jnp.bfloat16)
    y = deconv(x, w, 2, 1, preferred_element_type=jnp.float32)
    assert y.dtype == jnp.float32
    ref = deconv_reference(x.astype(jnp.float32), w.astype(jnp.float32),
                           2, 1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=3e-2, atol=3e-2)
    gx, gw = jax.grad(
        lambda x, w: jnp.sum(
            deconv(x, w, 2, 1, preferred_element_type=jnp.float32) ** 2),
        (0, 1))(x, w)
    assert gx.dtype == x.dtype and gw.dtype == w.dtype


def test_jit_and_vmap_compose(rng):
    x = jnp.asarray(rng.randn(2, 4, 4, 4), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 4, 4), jnp.float32)
    f = jax.jit(lambda x, w: deconv(x, w, 2, 1))
    np.testing.assert_allclose(np.asarray(f(x, w)),
                               np.asarray(deconv_reference(x, w, 2, 1)),
                               rtol=1e-4, atol=1e-4)
