"""First-class strided-conv subsystem vs ``lax.conv_general_dilated``:
rank/stride/padding sweeps, gradients, the shared planner, and the
structural on-engine guarantees (interpret mode on CPU)."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.jaxpr_utils import count_prims as _count_prims
from repro.core.jaxpr_utils import pallas_eqns as _pallas_eqns
from repro.core import conv_nd, conv_output_shape
from repro.core.engine import EngineConfig, UniformEngine
from repro.core.tiling import plan_uniform_tiles
from repro.kernels.conv import conv, conv_reference
from repro.kernels.conv.kernel import vmem_bytes as conv_vmem_bytes
from repro.kernels.conv.ref import conv_loop_oracle
from repro.kernels.common import fold_width
from repro.obs import Telemetry

# The satellite acceptance sweep: rank {1,2,3} x stride {1,2} x padding
# {0, 1, (0,1)} — every cell parity-checked against the XLA conv engine.
SWEEP = [
    (rank, stride, pad)
    for rank in (1, 2, 3)
    for stride in (1, 2)
    for pad in (0, 1, "lohi")
]


def _sweep_case(rng, rank, stride, pad):
    I = {1: (12,), 2: (9, 8), 3: (7, 6, 5)}[rank]
    K = (3,) * rank
    padding = ((0, 1),) * rank if pad == "lohi" else pad
    x = jnp.asarray(rng.randn(2, *I, 3), jnp.float32)
    w = jnp.asarray(rng.randn(*K, 3, 4), jnp.float32)
    return x, w, (stride,) * rank, padding


# In-plane tap folding: a lane-thin output block (cout < 128) folds each
# phase's in-plane taps into the contraction; at cout 128 every tap stays
# on the output columns.  name -> (rank, stride, pad, cin, cout, extra);
# extra: kernel, dilation, groups, int8 operands, a VMEM budget that
# forces the multi-tile grid, and whether the geometry must fold.
FOLD_SWEEP = {
    "fold-r3-s1-cin1": (3, 1, 1, 1, 16, {}),
    "fold-r3-s1-cin32": (3, 1, 1, 32, 16, {}),
    "fold-r2-s1-cin32": (2, 1, 1, 32, 16, {}),
    "fold-r2-s2-cin1": (2, 2, 1, 1, 16, {}),
    "fold-r3-s2-cin32": (3, 2, "lohi", 32, 16, {}),
    "fold-r2-s1-deep-halo": (2, 1, 1, 2, 16,
                             dict(kernel=(7, 3), max_tile_bytes=8 * 1024)),
    "fold-r3-dilation2": (3, 1, 2, 8, 16, dict(dilation=2)),
    "fold-r2-groups2": (2, 1, 1, 32, 16, dict(groups=2)),
    "fold-r3-int8": (3, 1, 1, 32, 16, dict(int8=True)),
    "nofold-r3-cout128": (3, 1, 1, 32, 128, dict(fold=False)),
}


def _fold_case(rng, rank, stride, pad, cin, cout, extra):
    I = {2: (19, 5), 3: (7, 6, 5)}[rank] if "max_tile_bytes" in extra \
        else {2: (9, 8), 3: (6, 5, 4)}[rank]
    K = extra.get("kernel", (3,) * rank)
    g = extra.get("groups", 1)
    padding = ((0, 1),) * rank if pad == "lohi" else pad
    x = rng.randn(2, *I, cin)
    w = rng.randn(*K, cin // g, cout)
    kw = {k: extra[k] for k in ("dilation", "groups") if k in extra}
    if extra.get("int8"):
        x, w = (jnp.asarray(np.clip(np.round(40 * a), -127, 127), jnp.int8)
                for a in (x, w))
        kw["w_scale"] = jnp.asarray(0.01 + rng.rand(cout), jnp.float32)
    else:
        x, w = jnp.asarray(x, jnp.float32), jnp.asarray(w, jnp.float32)
    return x, w, (stride,) * rank, padding, kw


CONV_CASES = (
    [pytest.param(*case, None, id=f"{case[0]}-{case[1]}-{case[2]}")
     for case in SWEEP]
    + [pytest.param(None, None, None, name, id=name) for name in FOLD_SWEEP])


@pytest.mark.parametrize("rank,stride,pad,fold_case", CONV_CASES)
def test_conv_matches_lax(rng, rank, stride, pad, fold_case):
    if fold_case is None:
        x, w, S, P = _sweep_case(rng, rank, stride, pad)
        ref = conv_reference(x, w, S, P)
        got = conv(x, w, S, P)
        assert got.shape == ref.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        return
    *geom, extra = FOLD_SWEEP[fold_case]
    x, w, S, P, kw = _fold_case(rng, *geom, extra)
    tel = Telemetry()
    eng = UniformEngine(EngineConfig(
        method="pallas", telemetry=tel,
        max_tile_bytes=extra.get("max_tile_bytes")))
    got = eng.conv(x, w, S, P, **kw)
    # int8 operands: the integer sums are exact in f32, then one rescale
    scale = kw.pop("w_scale", 1.0)
    ref = UniformEngine("xla").conv(x.astype(jnp.float32),
                                    w.astype(jnp.float32), S, P, **kw) * scale
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # which body ran: the engine counts the folded geometries it plans
    folded = tel.registry.get("engine_conv_tap_fold_total")
    assert (folded.value if folded else 0) == int(extra.get("fold", True))
    (key, plan), = eng.plan_cache.items()
    assert (fold_width(key[2], key[3], key[7], plan.block_co) > 0) == \
        extra.get("fold", True)
    if "max_tile_bytes" in extra:
        assert plan.n_dtiles > 1


@pytest.mark.parametrize("rank,stride,pad", [(2, 2, 1), (3, 1, "lohi"),
                                             (1, 2, 0), (3, 2, 1)])
def test_conv_gradients_match_lax_autodiff(rng, rank, stride, pad):
    x, w, S, P = _sweep_case(rng, rank, stride, pad)

    def f_pallas(x, w):
        return jnp.sum(jnp.sin(conv(x, w, S, P)))

    def f_ref(x, w):
        return jnp.sum(jnp.sin(conv_reference(x, w, S, P)))

    gp = jax.grad(f_pallas, (0, 1))(x, w)
    gr = jax.grad(f_ref, (0, 1))(x, w)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_conv_loop_oracle_anchor(rng):
    """The lax parity target itself agrees with the literal-definition
    python loop on a tiny shape (correlation convention, (lo,hi) pads)."""
    x = jnp.asarray(rng.randn(1, 5, 4, 2), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 2, 3), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(conv_reference(x, w, 2, ((1, 0), (0, 1)))),
        np.asarray(conv_loop_oracle(x, w, 2, ((1, 0), (0, 1)))),
        rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 3e-2)])
def test_conv_dtypes(rng, dtype, tol):
    x = jnp.asarray(rng.randn(2, 8, 8, 8), dtype)
    w = jnp.asarray(rng.randn(3, 3, 8, 8) * 0.2, dtype)
    ref = np.asarray(conv_reference(x.astype(jnp.float32),
                                    w.astype(jnp.float32), 2, 1))
    got = np.asarray(conv(x, w, 2, 1)).astype(np.float32)
    np.testing.assert_allclose(got, ref, rtol=tol, atol=tol * 3)


def test_conv_preferred_element_type(rng):
    """bf16 inputs emit f32 without a second rounding when asked — the
    in-kernel accumulator is f32 already."""
    x = jnp.asarray(rng.randn(1, 6, 6, 4), jnp.bfloat16)
    w = jnp.asarray(rng.randn(3, 3, 4, 4) * 0.2, jnp.bfloat16)
    y = conv(x, w, 2, 1, preferred_element_type=jnp.float32)
    assert y.dtype == jnp.float32
    ref = conv_reference(x, w, 2, 1, preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=3e-2, atol=3e-2)


def test_conv_multitile_is_single_pallas_call(rng):
    """A tiny VMEM budget forces the multi-tile grid; the forward is still
    ONE pallas_call with no stitching, and matches the oracle."""
    x = jnp.asarray(rng.randn(1, 33, 8, 3), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 3, 5), jnp.float32)
    plan = plan_uniform_tiles((35, 1, 10), (3, 1, 3), (2, 1, 2), 3, 5,
                              mode="conv", vmem_budget=4 * 1024)
    assert plan.n_dtiles > 1
    got = conv(x, w, 2, 1, max_tile_bytes=4 * 1024)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(conv_reference(x, w, 2, 1)),
                               rtol=1e-4, atol=1e-4)
    jaxpr = jax.make_jaxpr(
        lambda x, w: conv(x, w, 2, 1, max_tile_bytes=4 * 1024))(x, w)
    counts = _count_prims(jaxpr.jaxpr, {})
    assert counts.get("pallas_call") == 1, counts
    assert "dynamic_update_slice" not in counts, counts


def test_conv_multitile_stride1_deep_halo(rng):
    """Stride 1 (single phase, all K^d taps in one matmul) with the tile
    smaller than the K-1 halo: the reversed carry must compose recursively."""
    x = jnp.asarray(rng.randn(1, 19, 5, 2), jnp.float32)
    w = jnp.asarray(rng.randn(7, 3, 2, 2), jnp.float32)
    got = conv(x, w, 1, 1, max_tile_bytes=8 * 1024)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(conv_reference(x, w, 1, 1)),
                               rtol=1e-4, atol=1e-4)


def test_conv_backward_is_pallas(rng):
    """The adjoint loop closes on-engine: the traced conv backward is
    served by pallas_calls (fwd + dx-as-deconv + dw), with NO dot_general
    or conv_general_dilated outside the accelerator kernels."""
    x = jnp.asarray(rng.randn(1, 12, 6, 6, 2), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 3, 2, 2), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, w: jnp.sum(conv(x, w, 2, 1, max_tile_bytes=48 * 1024)),
        (0, 1)))(x, w)
    counts = _count_prims(jaxpr.jaxpr, {}, into_pallas=False)
    assert counts.get("pallas_call") == 3, counts   # fwd + dx + dw
    assert "dot_general" not in counts, counts
    assert "conv_general_dilated" not in counts, counts


@pytest.mark.parametrize("rank,K,S", [(3, (3, 3, 3), (2, 2, 2)),
                                      (2, (3, 3), (1, 1))])
def test_conv_matmuls_are_tap_batched(rng, rank, K, S):
    """S^d wide MXU dispatches per grid step — a single matmul carries all
    K^d taps when stride is 1."""
    I = (8,) * rank
    x = jnp.asarray(rng.randn(1, *I, 4), jnp.float32)
    w = jnp.asarray(rng.randn(*K, 4, 4), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x, w: conv(x, w, S, 1))(x, w)
    calls = _pallas_eqns(jaxpr.jaxpr, [])
    assert len(calls) == 1, len(calls)
    dots = _count_prims(calls[0].params["jaxpr"], {}).get("dot_general", 0)
    assert dots == math.prod(S), (dots, math.prod(S), math.prod(K))


def test_plan_conv_mode_respects_budget():
    plan = plan_uniform_tiles((66, 16, 16), (3, 3, 3), (2, 2, 2), 128, 256,
                              mode="conv", vmem_budget=1 << 20)
    assert plan.step_vmem_bytes <= 1 << 20 or (
        plan.dtile == 1 and plan.block_ci == 8 and plan.block_co == 8)
    out_sp = conv_output_shape((66, 16, 16), 3, 2)
    assert plan.n_dtiles * plan.dtile >= out_sp[0] + 1  # output + halo slack
    assert conv_vmem_bytes(out_sp, (3, 3, 3), (2, 2, 2),
                           plan.block_ci, plan.block_co,
                           dtile=plan.dtile) <= plan.step_vmem_bytes
    # the training plan budgets max(fwd, dx-as-deconv, dw) — it may choose
    # SMALLER blocks than the forward plan, but must still meet the budget
    train = plan_uniform_tiles((66, 16, 16), (3, 3, 3), (2, 2, 2), 128, 256,
                               mode="conv", vmem_budget=1 << 20,
                               backward=True)
    assert train.step_vmem_bytes <= 1 << 20 or (
        train.dtile == 1 and train.block_ci == 8 and train.block_co == 8)
    assert train.n_dtiles * train.dtile >= out_sp[0] + 1


def test_conv_nd_dispatch(rng):
    """The engine front-end: 'xla' and 'pallas' agree; unknown names raise."""
    x = jnp.asarray(rng.randn(2, 9, 9, 3), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 3, 4), jnp.float32)
    ref = conv_nd(x, w, 2, 1, method="xla")
    got = conv_nd(x, w, 2, 1, method="pallas")
    assert got.shape == ref.shape == (2, *conv_output_shape((9, 9), 3, 2, 1),
                                      4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError):
        conv_nd(x, w, 2, 1, method="oom")
