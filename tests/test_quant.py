"""Quantized engine paths: int8 kernels with fused per-channel dequant
behind the one ``Precision`` policy API.

Pins the tentpole contracts:

* int8-weight parity vs f32 within calibration tolerance across
  rank {2,3} x stride {1,2} x {dense, grouped, dilated} x fused epilogues
  — and EXACT parity vs the float op on dequantized weights (the fused
  epilogue scale commutes with the ci/tap contraction).
* per-channel scales reconstruct no worse than per-tensor.
* VJP: f32-exact gradients vs the dequantized-weight reference (dx, db),
  the dscale fold, and the NotImplementedError wall behind quantized
  activations.
* the planner byte model: int8 weights shrink the modeled step working
  set by exactly the weight-slab bytes at identical blocks and identical
  dispatch counts; strict_vmem accepts quantized plans a nominal-width
  budget would reject.
* Precision / EngineConfig compat-shim validation at CONFIG time.
* compiled networks: dispatch counts equal to f32, zero extra multiplies
  outside the kernels (the dequant is fused), quantized entries accepted
  by chains and graphs, rejected by channel-partitioned chains.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import quant
from repro.core import (
    EngineConfig,
    Precision,
    ScheduleError,
    UniformEngine,
    VmemBudgetError,
    compile_network,
    init_network_weights,
)
from repro.core import networks, tiling
from repro.core.jaxpr_utils import count_prims
from repro.core.networks import Epilogue, UniformLayer, deconv_stack
from repro.kernels.common import tile_bytes
from repro.kernels.deconv.kernel import vmem_bytes as deconv_vmem_bytes

ENGINE = UniformEngine(EngineConfig(method="pallas"))


def _deq(q):
    return q["w_q"].astype(jnp.float32) * q["scale"]


# ---------------------------------------------------------------------------
# Parity matrix: rank x stride x variant x epilogue
# ---------------------------------------------------------------------------

MATRIX = [
    (rank, stride, variant, epi)
    for rank in (2, 3)
    for stride in (1, 2)
    for variant in ("dense", "grouped", "dilated")
    for epi in ("none", "bias_relu")
]


def _matrix_case(rng, rank, stride, variant):
    I = {2: (5, 4), 3: (4, 3, 3)}[rank]
    K = (3,) * rank
    S = (stride,) * rank
    crop = ((0, 1),) * rank if stride == 2 else 0
    groups = 2 if variant == "grouped" else 1
    dil = 2 if variant == "dilated" else 1
    ci, co = 4, 8
    x = jnp.asarray(rng.randn(2, *I, ci), jnp.float32)
    w = jnp.asarray(0.2 * rng.randn(*K, ci // groups, co), jnp.float32)
    return x, w, S, crop, groups, dil


@pytest.mark.parametrize("rank,stride,variant,epi", MATRIX)
def test_int8_weight_parity(rng, rank, stride, variant, epi):
    x, w, S, crop, groups, dil = _matrix_case(rng, rank, stride, variant)
    q = quant.quantize_tensor(w)
    b = (jnp.asarray(0.1 * rng.randn(w.shape[-1]), jnp.float32)
         if epi == "bias_relu" else None)
    act = "relu" if epi == "bias_relu" else "none"
    kw = dict(dilation=dil, groups=groups, bias=b, activation=act)
    y_q = ENGINE.deconv(x, q["w_q"], S, crop, w_scale=q["scale"], **kw)
    y_deq = ENGINE.deconv(x, _deq(q), S, crop, **kw)
    y_f32 = ENGINE.deconv(x, w, S, crop, **kw)
    # fused dequant == dequantize-then-float-op, bit-for-bit up to f32
    np.testing.assert_allclose(np.asarray(y_q), np.asarray(y_deq),
                               rtol=1e-5, atol=2e-5)
    # and within calibration tolerance of full precision (documented: 5%
    # of the output range for symmetric absmax per-cout int8)
    tol = 0.05 * float(jnp.max(jnp.abs(y_f32))) + 1e-6
    assert float(jnp.max(jnp.abs(y_q - y_f32))) <= tol


def test_int8_weight_parity_conv(rng):
    x = jnp.asarray(rng.randn(2, 6, 6, 4), jnp.float32)
    w = jnp.asarray(0.2 * rng.randn(3, 3, 4, 8), jnp.float32)
    q = quant.quantize_tensor(w)
    b = jnp.asarray(0.1 * rng.randn(8), jnp.float32)
    y_q = ENGINE.conv(x, q["w_q"], 2, 1, w_scale=q["scale"], bias=b,
                      activation="relu")
    y_deq = ENGINE.conv(x, _deq(q), 2, 1, bias=b, activation="relu")
    np.testing.assert_allclose(np.asarray(y_q), np.asarray(y_deq),
                               rtol=1e-5, atol=2e-5)


def test_xla_engine_matches_pallas_on_quantized_weights(rng):
    x = jnp.asarray(rng.randn(1, 5, 4, 4), jnp.float32)
    w = jnp.asarray(0.2 * rng.randn(3, 3, 4, 8), jnp.float32)
    q = quant.quantize_tensor(w)
    kw = dict(w_scale=q["scale"], activation="relu")
    y_p = ENGINE.deconv(x, q["w_q"], 2, ((0, 1), (0, 1)), **kw)
    y_x = UniformEngine("iom_phase").deconv(x, q["w_q"], 2,
                                            ((0, 1), (0, 1)), **kw)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_x),
                               rtol=1e-4, atol=1e-4)


def test_per_channel_beats_per_tensor(rng):
    # widely varying per-channel magnitudes: one shared scale clips the
    # small channels' resolution, per-cout scales do not
    x = jnp.asarray(rng.randn(1, 5, 5, 4), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 4, 8), jnp.float32)
    w = w * (10.0 ** jnp.arange(-3, 5, dtype=jnp.float32))
    y_ref = ENGINE.deconv(x, w, 2, ((0, 1), (0, 1)))

    s_pc = quant.absmax_scale(w, axis=-1)
    s_pt = quant.absmax_scale(w)            # per-tensor scalar
    err = {}
    for name, s in (("pc", s_pc), ("pt", s_pt)):
        wq = quant.quantize_q8(w, s)
        y = ENGINE.deconv(x, wq, 2, ((0, 1), (0, 1)), w_scale=s)
        err[name] = float(jnp.max(jnp.abs(y - y_ref)))
    assert err["pc"] <= err["pt"]


# ---------------------------------------------------------------------------
# Gradients
# ---------------------------------------------------------------------------

def test_vjp_matches_dequantized_reference(rng):
    x = jnp.asarray(rng.randn(1, 5, 4, 4), jnp.float32)
    w = jnp.asarray(0.2 * rng.randn(3, 3, 4, 8), jnp.float32)
    b = jnp.asarray(0.1 * rng.randn(8), jnp.float32)
    q = quant.quantize_tensor(w)
    w_deq = _deq(q)
    kw = dict(activation="relu")

    def f_q(x, s, b):
        y = ENGINE.deconv(x, q["w_q"], 2, ((0, 1), (0, 1)),
                          w_scale=s, bias=b, **kw)
        return jnp.sum(y ** 2)

    def f_ref(x, w, b):
        y = ENGINE.deconv(x, w, 2, ((0, 1), (0, 1)), bias=b, **kw)
        return jnp.sum(y ** 2)

    dx_q, ds, db_q = jax.grad(f_q, argnums=(0, 1, 2))(x, q["scale"], b)
    dx_r, dw_r, db_r = jax.grad(f_ref, argnums=(0, 1, 2))(x, w_deq, b)
    # dx and db are f32-exact: the backward runs the SAME Pallas kernels
    # on the dequantized weights
    np.testing.assert_allclose(np.asarray(dx_q), np.asarray(dx_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(db_q), np.asarray(db_r),
                               rtol=1e-5, atol=1e-5)
    # the scale gradient is the per-cout fold of the dequantized-weight
    # gradient: dscale[c] = sum_{taps, ci} w_q * dw_deq
    ds_ref = jnp.sum(q["w_q"].astype(jnp.float32) * dw_r, axis=(0, 1, 2))
    np.testing.assert_allclose(np.asarray(ds), np.asarray(ds_ref),
                               rtol=1e-4, atol=1e-4)


def test_backward_through_quantized_activations_raises(rng):
    x = jnp.asarray(rng.randn(1, 5, 4, 4), jnp.float32)
    w = jnp.asarray(0.2 * rng.randn(3, 3, 4, 8), jnp.float32)
    q = quant.quantize_tensor(w)
    eng = UniformEngine(EngineConfig(
        method="pallas",
        precision=Precision(weight_quant="int8", act_quant="int8")))
    # forward runs (dynamic per-tensor act quant, scale folded into the
    # epilogue); the backward is explicitly unsupported
    y = eng.deconv(x, q["w_q"], 2, ((0, 1), (0, 1)), w_scale=q["scale"])
    assert y.shape == (1, 10, 8, 8)
    with pytest.raises(NotImplementedError, match="quantized activations"):
        jax.grad(lambda xx: jnp.sum(eng.deconv(
            xx, q["w_q"], 2, ((0, 1), (0, 1)), w_scale=q["scale"])))(x)


# ---------------------------------------------------------------------------
# Planner byte model + strict_vmem
# ---------------------------------------------------------------------------

def test_byte_model_charges_int8_weight_width():
    sp, k, s = (8, 1, 8), (3, 1, 3), (2, 1, 2)
    p16 = tiling.plan_uniform_tiles(sp, k, s, 64, 64, mode="deconv")
    p8 = tiling.plan_uniform_tiles(sp, k, s, 64, 64, mode="deconv",
                                   w_dtype_bytes=1)
    # same blocks -> the delta is EXACTLY the weight slab's saved bytes
    # (double-buffered, in the tiled VMEM layout)
    assert (p16.dtile, p16.block_ci, p16.block_co) == \
        (p8.dtile, p8.block_ci, p8.block_co)
    saved = 2 * 3 * 1 * 3 * (tile_bytes(p16.block_ci, p16.block_co, 2)
                             - tile_bytes(p16.block_ci, p16.block_co, 1))
    assert p16.step_vmem_bytes - p8.step_vmem_bytes == saved
    # dispatch counts are a function of blocks/grid only — identical
    t16 = tiling.plan_cost_terms(p16, sp, k, s, 64, 64, mode="deconv",
                                 groups=1, dilation=(1, 1, 1))
    t8 = tiling.plan_cost_terms(p8, sp, k, s, 64, 64, mode="deconv",
                                groups=1, dilation=(1, 1, 1))
    assert t16["mxu_dispatches"] == t8["mxu_dispatches"]
    assert t16["grid_steps"] == t8["grid_steps"]
    assert t8["hbm_bytes"] < t16["hbm_bytes"]


def test_weight_heavy_step_bytes_roughly_halve():
    # channel-dominated geometry: the weight slab IS the working set, so
    # int8 weights roughly halve the modeled step bytes
    b16 = deconv_vmem_bytes((2, 1, 2), (3, 1, 3), (2, 1, 2), 512, 512, 2)
    b8 = deconv_vmem_bytes((2, 1, 2), (3, 1, 3), (2, 1, 2), 512, 512, 2,
                           w_dtype_bytes=1)
    assert b8 < 0.62 * b16


def test_strict_vmem_accepts_quantized_plan():
    sp, k, s = (4, 1, 4), (3, 1, 3), (2, 1, 2)
    ci = co = 256
    blk = 128           # MXU-wide blocks: below ~32 rows int8 tiles pad
    # the minimal feasible working set at each width (budget 1 forces the
    # planner to its smallest plan, returned best-effort)
    lo8 = tiling.plan_uniform_tiles(sp, k, s, ci, co, mode="deconv",
                                    vmem_budget=1, w_dtype_bytes=1,
                                    block_ci=blk, block_co=blk)
    lo16 = tiling.plan_uniform_tiles(sp, k, s, ci, co, mode="deconv",
                                     vmem_budget=1, block_ci=blk,
                                     block_co=blk)
    assert lo8.step_vmem_bytes < lo16.step_vmem_bytes
    budget = (lo8.step_vmem_bytes + lo16.step_vmem_bytes) // 2
    eng = UniformEngine(EngineConfig(method="pallas", strict_vmem=True,
                                     max_tile_bytes=budget, block_ci=blk,
                                     block_co=blk))
    # int8 weights fit the budget ...
    plan = eng.plan("deconv", sp, k, s, ci, co, w_dtype_bytes=1)
    assert not plan.overflows
    # ... the nominal width does not
    with pytest.raises(VmemBudgetError):
        eng.plan("deconv", sp, k, s, ci, co)


def test_plan_key_grows_weight_width():
    eng = UniformEngine(EngineConfig(method="pallas"))
    eng.plan("deconv", (4, 1, 4), (3, 1, 3), (2, 1, 2), 8, 8)
    eng.plan("deconv", (4, 1, 4), (3, 1, 3), (2, 1, 2), 8, 8,
             w_dtype_bytes=1)
    keys = sorted(eng.plan_cache)
    assert len(keys) == 2 and all(len(k) == 11 for k in keys)
    assert {k[-1] for k in keys} == {1, 2}
    # the tuner's canonical string key mirrors the tuple field for field
    from repro import tune
    assert tune.plan_key("deconv", (4, 1, 4), (3, 1, 3), (2, 1, 2), 8, 8,
                         w_dtype_bytes=1) == tune.key_from_tuple(keys[0])
    geom = tune.LayerGeometry(mode="deconv", in_spatial=(4, 1, 4),
                              kernel=(3, 1, 3), stride=(2, 1, 2),
                              cin=8, cout=8, w_dtype_bytes=1)
    assert geom.key_tuple == keys[0]


# ---------------------------------------------------------------------------
# Precision policy + config validation
# ---------------------------------------------------------------------------

def test_precision_validates_at_config_time():
    with pytest.raises(ValueError, match="accumulate"):
        Precision(accumulate=jnp.bfloat16)
    with pytest.raises(ValueError, match="weight_quant"):
        Precision(weight_quant="int4")
    with pytest.raises(ValueError, match="act_quant"):
        Precision(act_quant="fp8")
    with pytest.raises(ValueError, match="requires weight_quant"):
        Precision(act_quant="int8")
    with pytest.raises(ValueError, match="channel_axis"):
        Precision(weight_quant="int8", channel_axis=0)
    with pytest.raises((TypeError, ValueError)):
        Precision(storage="not-a-dtype")
    assert Precision(weight_quant="int8").weight_bytes == 1
    assert Precision().weight_bytes == 2
    assert Precision(weight_quant="int8", act_quant="int8").act_bytes == 1


def test_engineconfig_compat_shim():
    legacy = EngineConfig(method="pallas",
                          preferred_element_type=jnp.bfloat16)
    new = EngineConfig(method="pallas",
                       precision=Precision(storage=jnp.bfloat16))
    # the two spellings are THE SAME config: equal, same hash, same
    # memoized default engine
    assert legacy == new and hash(legacy) == hash(new)
    assert legacy.precision == Precision(storage=jnp.bfloat16)
    assert new.preferred_element_type == jnp.dtype(jnp.bfloat16)
    # replace() round-trips a normalized config (both fields set, equal)
    again = dataclasses.replace(legacy, strict_vmem=True)
    assert again.precision.storage == jnp.dtype(jnp.bfloat16)
    with pytest.raises(ValueError, match="conflicts"):
        EngineConfig(preferred_element_type=jnp.float32,
                     precision=Precision(storage=jnp.bfloat16))
    with pytest.raises(ValueError, match="Precision"):
        EngineConfig(precision="int8")
    with pytest.raises(ValueError, match="precision"):
        UniformLayer(name="l", in_spatial=(4, 4), cin=4, cout=4,
                     kernel=(3, 3), stride=(2, 2), precision="int8")


# ---------------------------------------------------------------------------
# Calibration
# ---------------------------------------------------------------------------

def test_percentile_observer_ignores_outliers(rng):
    w = jnp.asarray(0.1 * rng.randn(3, 3, 4, 8), jnp.float32)
    w = w.at[0, 0, 0, 0].set(100.0)       # one rogue weight in channel 0
    s_abs = quant.absmax_observer(w)
    s_pct = quant.percentile_observer(w, pct=99.0)
    assert s_abs.shape == s_pct.shape == (8,)
    assert float(s_pct[0]) < float(s_abs[0])        # outlier clipped
    assert float(s_abs[0]) == pytest.approx(100.0 / 127.0)


def test_quantize_weights_structures(rng):
    prec = Precision(weight_quant="int8")
    w = jnp.asarray(0.2 * rng.randn(3, 3, 4, 8), jnp.float32)
    b = jnp.zeros((8,), jnp.float32)
    # graph dict with {"w", "b"} entries
    ws = {"a": {"w": w, "b": b}, "bare": w}
    out = quant.quantize_weights(ws, prec)
    assert set(out["a"]) == {"w_q", "scale", "b"}
    assert out["a"]["w_q"].dtype == jnp.int8
    assert out["a"]["scale"].shape == (8,)
    assert set(out["bare"]) == {"w_q", "scale"}
    # chain list
    lst = quant.quantize_weights([w, w], prec)
    assert isinstance(lst, list) and all("w_q" in e for e in lst)
    # no-quant policy is the identity
    assert quant.quantize_weights(ws, Precision()) is ws
    # already-quantized entries pass through
    again = quant.quantize_weights(out, prec)
    assert again["a"]["w_q"] is out["a"]["w_q"]
    with pytest.raises(ValueError, match="observer"):
        quant.quantize_tensor(w, observer="bogus")


def test_compress_dedups_onto_quant(rng):
    from repro.optim import compress
    # ONE int8 codepath: optim.compress re-exports repro.quant's helpers
    assert compress.quantize_int8 is quant.quantize_int8
    assert compress.dequantize_int8 is quant.dequantize_int8
    x = jnp.asarray(rng.randn(32), jnp.float32)
    q, scale = compress.quantize_int8(x)
    # historical formula, bit for bit
    s_ref = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q_ref = jnp.clip(jnp.round(x / s_ref), -127, 127).astype(jnp.int8)
    assert float(scale) == float(s_ref)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))


# ---------------------------------------------------------------------------
# Compiled networks
# ---------------------------------------------------------------------------

def _q8_chain(rng):
    # channels wide enough that int8 weight tiles (32 rows) are denser
    # than 16-bit ones in the tiled VMEM layout
    layers = deconv_stack("g", 2, 4, [64, 64, 4])
    ws = init_network_weights(layers, jax.random.PRNGKey(0))
    wq = quant.quantize_weights(ws, Precision(weight_quant="int8"))
    x = jnp.asarray(rng.randn(1, 4, 4, 64), jnp.float32)
    return layers, ws, wq, x


def test_compiled_chain_quantized_dispatch_and_bytes(rng):
    layers, ws, wq, x = _q8_chain(rng)
    eng_q = UniformEngine(EngineConfig(
        method="pallas", precision=Precision(weight_quant="int8")))
    eng_f = UniformEngine(EngineConfig(method="pallas"))
    apply_q, rep_q = compile_network(layers, eng_q, batch=1)
    apply_f, rep_f = compile_network(layers, eng_f, batch=1)
    # identical dispatch counts, strictly smaller modeled step bytes
    assert rep_q.mxu_dispatches == rep_f.mxu_dispatches
    assert rep_q.grid_steps == rep_f.grid_steps
    for rq, rf in zip(rep_q.layers, rep_f.layers):
        assert rq.vmem_bytes < rf.vmem_bytes
        assert rq.precision == "w:int8" and rf.precision == "f32"
    y_q = apply_q(wq, x)
    y_f = apply_f(ws, x)
    tol = 0.05 * float(jnp.max(jnp.abs(y_f))) + 1e-6
    assert float(jnp.max(jnp.abs(y_q - y_f))) <= tol

    jx_q = jax.make_jaxpr(apply_q)(wq, x)
    jx_f = jax.make_jaxpr(apply_f)(ws, x)
    out_q = count_prims(jx_q.jaxpr, into_pallas=False)
    out_f = count_prims(jx_f.jaxpr, into_pallas=False)
    # same kernel launches; the dequant adds ZERO multiplies and ZERO
    # dots outside the kernels — it lives in the fused epilogue
    assert out_q.get("pallas_call") == out_f.get("pallas_call")
    assert out_q.get("mul", 0) == out_f.get("mul", 0)
    assert out_q.get("dot_general", 0) == out_f.get("dot_general", 0)
    assert out_q.get("conv_general_dilated", 0) == 0
    # and the MXU work inside the kernels is structurally identical
    in_q = count_prims(jx_q.jaxpr, into_pallas=True)
    in_f = count_prims(jx_f.jaxpr, into_pallas=True)
    assert in_q.get("dot_general") == in_f.get("dot_general")


def test_compiled_graph_quantized_with_bias_epilogues(rng):
    relu = Epilogue(bias=True, activation="relu")
    layers = [dataclasses.replace(l, epilogue=relu)
              for l in deconv_stack("g", 2, 4, [6, 6, 4])]
    graph = networks.chain_graph(layers)
    ws = init_network_weights(graph, jax.random.PRNGKey(1))
    wq = quant.quantize_weights(ws, Precision(weight_quant="int8"))
    eng = UniformEngine(EngineConfig(
        method="pallas", precision=Precision(weight_quant="int8")))
    apply, report = compile_network(graph, eng, batch=1)
    x = jnp.asarray(rng.randn(1, 4, 4, 6), jnp.float32)
    y_q = apply(wq, x)
    y_f = apply(ws, x)
    assert all(r.precision == "w:int8" for r in report.layers)
    tol = 0.05 * float(jnp.max(jnp.abs(y_f))) + 1e-6
    assert float(jnp.max(jnp.abs(y_q - y_f))) <= tol


def test_per_layer_precision_override(rng):
    # body int8, head full-precision: the head row plans at nominal width
    layers = deconv_stack("g", 2, 4, [8, 8, 4])
    layers[-1] = dataclasses.replace(layers[-1], precision=Precision())
    eng = UniformEngine(EngineConfig(
        method="pallas", precision=Precision(weight_quant="int8")))
    _, report = compile_network(layers, eng, batch=1)
    assert report.layers[0].precision == "w:int8"
    assert report.layers[-1].precision == "f32"


def test_sharded_chain_rejects_quantized_entries(rng):
    from jax.sharding import Mesh
    layers, ws, wq, x = _q8_chain(rng)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
    eng = UniformEngine(EngineConfig(method="pallas", mesh=mesh))
    apply, _ = compile_network(layers, eng, batch=1)
    with pytest.raises(ScheduleError, match="bare weight arrays"):
        apply(wq, x)
