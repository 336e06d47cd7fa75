"""Layer-algebra parity matrix: grouped / depthwise / dilated conv AND
deconv (with fused bias+activation epilogues) against the lax oracles,
over rank x stride, values and VJPs — plus the planner's per-group block
budgeting (interpret mode on CPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import functional as F
from repro.core.tiling import plan_uniform_tiles
from repro.kernels.conv import ops as cops
from repro.kernels.deconv import ops as dops

# (dilation, groups): vanilla, dilated, grouped, both, depthwise
VARIANTS = [(1, 1), (2, 1), (1, 2), (2, 2), (1, 4)]
SPATIAL = {1: (13,), 2: (11, 9), 3: (7, 6, 5)}
KERNEL = {1: (4,), 2: (3, 3), 3: (3, 2, 2)}


def _lax_conv(x, w, stride, pad, dil, groups):
    rank = x.ndim - 2
    dn = lax.conv_dimension_numbers(x.shape, w.shape, F.dim_numbers(rank))
    return lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride,) * rank,
        padding=list(F.canon_padding(pad, rank)),
        rhs_dilation=(dil,) * rank, feature_group_count=groups,
        dimension_numbers=dn)


def _act(y, name, alpha=0.2):
    if name == "relu":
        return jnp.maximum(y, 0)
    if name == "leaky_relu":
        return jnp.where(y > 0, y, alpha * y)
    if name == "tanh":
        return jnp.tanh(y)
    return y


def _case(rng, rank, groups):
    ci, co = (4, 4) if groups == 4 else (4, 8)   # g==4 -> depthwise
    sp, k = SPATIAL[rank], KERNEL[rank]
    x = jnp.asarray(rng.randn(2, *sp, ci), jnp.float32)
    w = jnp.asarray(rng.randn(*k, ci // groups, co) * 0.3, jnp.float32)
    b = jnp.asarray(rng.randn(co), jnp.float32)
    return x, w, b


@pytest.mark.parametrize("rank", [1, 2, 3])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("dil,groups", VARIANTS)
def test_conv_matrix_matches_lax(rng, rank, stride, dil, groups):
    x, w, b = _case(rng, rank, groups)
    got = cops.conv(x, w, stride, 1, dilation=dil, groups=groups, bias=b,
                    activation="leaky_relu", interpret=True)
    ref = _act(_lax_conv(x, w, stride, 1, dil, groups) + b, "leaky_relu")
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rank", [1, 2, 3])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("dil,groups", VARIANTS)
def test_deconv_matrix_matches_lax(rng, rank, stride, dil, groups):
    x, w, b = _case(rng, rank, groups)
    got = dops.deconv(x, w, stride, 1, dilation=dil, groups=groups, bias=b,
                      activation="tanh", interpret=True)
    ref = _act(F.deconv_xla(x, w, stride, 1, dilation=dil, groups=groups)
               + b, "tanh")
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# Grads: one rank-2 and one rank-3 point per variant keeps interpret-mode
# runtime sane while still covering every (dilation, groups) transform.
@pytest.mark.parametrize("rank,stride", [(2, 2), (3, 1)])
@pytest.mark.parametrize("dil,groups", VARIANTS)
def test_conv_grads_match_lax(rng, rank, stride, dil, groups):
    x, w, b = _case(rng, rank, groups)

    def f_lax(x, w, b):
        return (_act(_lax_conv(x, w, stride, 1, dil, groups) + b,
                     "leaky_relu") ** 2).sum()

    def f_pallas(x, w, b):
        return (cops.conv(x, w, stride, 1, dilation=dil, groups=groups,
                          bias=b, activation="leaky_relu",
                          interpret=True) ** 2).sum()

    for ref, got in zip(jax.grad(f_lax, argnums=(0, 1, 2))(x, w, b),
                        jax.grad(f_pallas, argnums=(0, 1, 2))(x, w, b)):
        scale = 1.0 + float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(ref) / scale,
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rank,stride", [(2, 2), (3, 1)])
@pytest.mark.parametrize("dil,groups", VARIANTS)
def test_deconv_grads_match_lax(rng, rank, stride, dil, groups):
    x, w, b = _case(rng, rank, groups)

    def f_lax(x, w, b):
        return (_act(F.deconv_xla(x, w, stride, 1, dilation=dil,
                                  groups=groups) + b, "tanh") ** 2).sum()

    def f_pallas(x, w, b):
        return (dops.deconv(x, w, stride, 1, dilation=dil, groups=groups,
                            bias=b, activation="tanh",
                            interpret=True) ** 2).sum()

    for ref, got in zip(jax.grad(f_lax, argnums=(0, 1, 2))(x, w, b),
                        jax.grad(f_pallas, argnums=(0, 1, 2))(x, w, b)):
        scale = 1.0 + float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(ref) / scale,
                                   rtol=1e-4, atol=1e-4)


def test_planner_blocks_channels_per_group():
    """Grouped plans tile the PER-GROUP channel extents and still respect
    the VMEM budget the caller set."""
    budget = 1024 * 1024
    for groups in (2, 4):
        plan = plan_uniform_tiles((16, 16), (3, 3), (2, 2), 128, 256,
                                  groups=groups, vmem_budget=budget)
        assert plan.block_ci <= 128 // groups
        assert plan.block_co <= 256 // groups
        assert plan.step_vmem_bytes <= budget


def test_planner_depthwise_blocks_are_single_channel():
    plan = plan_uniform_tiles((8, 8), (3, 3), (2, 2), 64, 64, groups=64,
                              vmem_budget=512 * 1024)
    assert plan.block_ci == 1 and plan.block_co == 1


def test_dilated_plan_budgets_effective_kernel():
    """A dilated kernel's halo is (K-1)*d deep — the plan's working set
    must reflect the EFFECTIVE kernel, so the dilated plan can never be
    cheaper than the dense one at the same geometry."""
    # a budget both fit whole, so the two plans tile identically
    budget = 64 << 20
    dense = plan_uniform_tiles((32, 32), (3, 3), (2, 2), 64, 64,
                               vmem_budget=budget)
    dil = plan_uniform_tiles((32, 32), (3, 3), (2, 2), 64, 64,
                             dilation=(2, 2), vmem_budget=budget)
    assert dense.n_dtiles == dil.n_dtiles == 1
    assert dil.step_vmem_bytes >= dense.step_vmem_bytes
