"""DAG compilation on the uniform engine: ``UniformGraph`` scheduling with
merge nodes, fused epilogues traced INSIDE the kernels, grouped/dilated
rows in the ``ScheduleReport``, the bf16 storage-dtype contract, and the
batch-sharded graph path (interpret mode on CPU; 8-way tests run under the
tier1-multidevice CI job)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import (
    EngineConfig,
    UniformEngine,
    compile_network,
    init_network_weights,
    networks,
)
from repro.core.jaxpr_utils import count_prims
from repro.launch.mesh import make_host_mesh
from repro.models import dcnn as D
from repro.sharding.partition import split_params

KEY = jax.random.PRNGKey(0)
N_DEV = len(jax.devices())

needs8 = pytest.mark.skipif(
    N_DEV < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the tier1-multidevice CI job)")


def _small_vnet_graph():
    return networks.vnet_graph(in_spatial=(8, 8, 8), chans=(2, 4, 8),
                               cin=1, num_classes=2)


# ---------------------------------------------------------------------------
# Graph structure + schedule report
# ---------------------------------------------------------------------------

def test_vnet_graph_schedules_merges_and_epilogues():
    graph = _small_vnet_graph()
    eng = UniformEngine(method="pallas")
    _, report = compile_network(graph, eng, batch=1)
    rows = {l.name: l for l in report.layers}
    # 3 enc + 2 up + 2 merge-conv + head layers, plus 2 concat merge nodes
    assert len(report.layers) == 3 + 2 + 2 + 1 + 2
    skips = [l for l in report.layers if l.op == "concat"]
    assert len(skips) == 2
    for l in skips:
        assert l.plan is None
        assert l.grid_steps == 0 and l.mxu_dispatches == 0
    # fused epilogues and the new columns appear in describe()
    assert rows["vnet.enc1"].epilogue == "bias-free relu" \
        or "relu" in rows["vnet.enc1"].epilogue
    text = report.describe()
    assert "ep:" in text and "concat" in text
    # lane-thin 3x3x3 convs fold their in-plane taps; the 1x1x1 head has
    # one in-plane tap and the deconvs run their own body
    assert rows["vnet.enc1"].tap_fold and rows["vnet.merge2"].tap_fold
    assert not rows["vnet.head"].tap_fold
    assert not any(l.tap_fold for l in report.layers if l.op == "deconv")
    assert text.count("+fold") == sum(l.tap_fold for l in report.layers)
    js = report.to_json()
    for row in js["layers"]:
        assert {"groups", "dilation", "epilogue", "tap_fold"} <= set(row)


def test_graph_report_carries_groups_and_dilation():
    lay = networks.UniformLayer(
        name="g.dw", in_spatial=(12, 12), cin=8, cout=8, kernel=(3, 3),
        stride=(1, 1), padding=((2, 2),) * 2, op="conv", groups=8,
        dilation=(2, 2),
        epilogue=networks.Epilogue(bias=True, activation="relu"))
    graph = networks.chain_graph([lay])
    _, report = compile_network(graph, UniformEngine(method="pallas"))
    row = report.layers[0]
    assert row.groups == 8 and row.dilation == (2, 2)
    assert "relu" in row.epilogue
    assert "g8" in report.describe() and "d2x2" in report.describe()


def test_graph_weight_dict_validation():
    graph = _small_vnet_graph()
    eng = UniformEngine(method="pallas")
    apply, _ = compile_network(graph, eng, batch=1)
    ws = init_network_weights(graph, KEY)
    x = jnp.zeros((1, 8, 8, 8, 1), jnp.float32)
    missing = dict(ws)
    missing.pop("vnet.head")
    with pytest.raises(ValueError, match="vnet.head"):
        apply(missing, x)
    # a bias-declaring epilogue demands {"w", "b"}
    lay = networks.UniformLayer(
        name="solo", in_spatial=(4, 4), cin=2, cout=2, kernel=(3, 3),
        stride=(2, 2), padding=((0, 1),) * 2, op="deconv",
        epilogue=networks.Epilogue(bias=True, activation="relu"))
    bgraph = networks.chain_graph([lay])
    bapply, _ = compile_network(bgraph, eng)
    bws = init_network_weights(bgraph, KEY)
    assert isinstance(bws["solo"], dict) and {"w", "b"} <= set(bws["solo"])
    with pytest.raises(ValueError, match="bias"):
        bapply({"solo": bws["solo"]["w"]}, jnp.zeros((1, 4, 4, 2)))


def test_init_network_weights_matches_graph_shapes():
    graph = _small_vnet_graph()
    ws = init_network_weights(graph, KEY)
    for lay in graph.layers:
        entry = ws[lay.name]
        w = entry["w"] if isinstance(entry, dict) else entry
        assert w.shape == lay.weight_shape
        if lay.epilogue.bias:
            assert entry["b"].shape == (lay.cout,)


# ---------------------------------------------------------------------------
# Numerics: epilogues execute inside the kernels
# ---------------------------------------------------------------------------

def test_graph_pallas_matches_xla_engine(rng):
    graph = _small_vnet_graph()
    ws = init_network_weights(graph, KEY)
    x = jnp.asarray(rng.randn(2, 8, 8, 8, 1) * 0.3, jnp.float32)
    ref_fn, _ = compile_network(graph, UniformEngine(method="iom_phase"))
    fn, _ = compile_network(graph, UniformEngine(method="pallas"))
    np.testing.assert_allclose(np.asarray(fn(ws, x)),
                               np.asarray(ref_fn(ws, x)),
                               rtol=1e-4, atol=1e-4)


def test_graph_grads_flow_through_merges(rng):
    graph = _small_vnet_graph()
    ws = init_network_weights(graph, KEY)
    x = jnp.asarray(rng.randn(1, 8, 8, 8, 1) * 0.3, jnp.float32)
    ref_fn, _ = compile_network(graph, UniformEngine(method="iom_phase"))
    fn, _ = compile_network(graph, UniformEngine(method="pallas"))
    g_ref = jax.grad(lambda w: (ref_fn(w, x) ** 2).sum())(ws)
    g_got = jax.grad(lambda w: (fn(w, x) ** 2).sum())(ws)
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_got)):
        scale = 1.0 + float(jnp.max(jnp.abs(a)))
        np.testing.assert_allclose(np.asarray(b) / scale,
                                   np.asarray(a) / scale,
                                   rtol=1e-3, atol=1e-3)


def test_graph_traces_no_elementwise_outside_kernels():
    """Acceptance: a compiled graph's jaxpr has ZERO conv_general_dilated
    and ZERO outside-kernel bias/activation ops — merges (concatenate) are
    the only array ops between pallas_calls."""
    graph = _small_vnet_graph()
    ws = init_network_weights(graph, KEY)
    x = jnp.zeros((1, 8, 8, 8, 1), jnp.float32)
    fn, _ = compile_network(graph, UniformEngine(method="pallas"))
    counts = count_prims(jax.make_jaxpr(fn)(ws, x).jaxpr, {},
                         into_pallas=False)
    assert counts.get("conv_general_dilated", 0) == 0, counts
    assert counts.get("dot_general", 0) == 0, counts
    assert counts.get("max", 0) == 0, counts          # relu is fused
    assert counts.get("tanh", 0) == 0, counts
    assert counts.get("pallas_call") == 8, counts     # 3+2+2+1 layer nodes
    assert counts.get("concatenate") == 2, counts     # the skip merges


def test_vnet_bf16_stays_bf16_end_to_end(rng):
    """The decoder used to astype every activation back per-layer; the
    graph walk owns the storage dtype instead — a bf16 volume produces
    bf16 logits with NO convert_element_type between kernels, and tracks
    the f32 forward."""
    cfg = get_config("vnet").reduced()
    params, _ = split_params(D.init_vnet(cfg, KEY))
    vol = jnp.asarray(rng.randn(1, *D._vnet_spatial(cfg), 1) * 0.3,
                      jnp.float32)
    ref = D.vnet_forward(params, cfg, vol, engine="pallas")
    p16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    got = D.vnet_forward(p16, cfg, vol.astype(jnp.bfloat16),
                         engine="pallas")
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref), rtol=5e-2, atol=5e-2)


def test_generator_tanh_fused_into_epilogue():
    """|img| <= 1 (tanh runs in the last deconv's epilogue) and the only
    host-side activation left is the z-projection relu."""
    cfg = get_config("dcgan").reduced()
    params, _ = split_params(D.init_generator(cfg, KEY))
    z = jnp.asarray(np.random.RandomState(0).randn(2, cfg.dcnn_z),
                    jnp.float32)
    img = D.generator_forward(params, cfg, z, engine="pallas")
    assert float(jnp.max(jnp.abs(img))) <= 1.0 + 1e-6
    counts = count_prims(jax.make_jaxpr(
        lambda p, z: D.generator_forward(p, cfg, z, engine="pallas"))(
            params, z).jaxpr, {}, into_pallas=False)
    assert counts.get("tanh", 0) == 0, counts
    assert counts.get("max", 0) <= 1, counts          # the proj relu only


# ---------------------------------------------------------------------------
# Sharded graphs (batch DP; weights replicated across skip merges)
# ---------------------------------------------------------------------------

def test_sharded_graph_host_mesh_parity(rng):
    mesh = make_host_mesh()
    dp = mesh.shape["data"]
    graph = _small_vnet_graph()
    ws = init_network_weights(graph, KEY)
    x = jnp.asarray(rng.randn(dp, 8, 8, 8, 1) * 0.3, jnp.float32)
    base_fn, _ = compile_network(graph, UniformEngine(method="pallas"))
    eng = UniformEngine(EngineConfig(method="pallas", mesh=mesh))
    fn, report = compile_network(graph, eng, batch=dp)
    np.testing.assert_allclose(np.asarray(jax.jit(fn)(ws, x)),
                               np.asarray(base_fn(ws, x)),
                               rtol=1e-4, atol=1e-4)
    assert report.data_parallel == dp
    assert report.per_device_batch == 1


@needs8
def test_sharded_graph_8way_dp_parity(rng):
    """The acceptance mesh: the V-Net DAG (skips included) under 8-way
    batch DP matches the unsharded graph at 1e-4 and stays conv-free."""
    mesh = make_host_mesh()                      # (8, 1)
    graph = _small_vnet_graph()
    ws = init_network_weights(graph, KEY)
    x = jnp.asarray(rng.randn(8, 8, 8, 8, 1) * 0.3, jnp.float32)
    base_fn, _ = compile_network(graph, UniformEngine(method="pallas"))
    eng = UniformEngine(EngineConfig(method="pallas", mesh=mesh))
    fn, report = compile_network(graph, eng, batch=8)
    np.testing.assert_allclose(np.asarray(jax.jit(fn)(ws, x)),
                               np.asarray(base_fn(ws, x)),
                               rtol=1e-4, atol=1e-4)
    assert report.data_parallel == 8
    counts = count_prims(jax.make_jaxpr(fn)(ws, x).jaxpr, {},
                         into_pallas=False)
    assert counts.get("conv_general_dilated", 0) == 0, counts
