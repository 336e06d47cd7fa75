"""Pallas-kernel microbenchmark (interpret mode on CPU): per-method
wall-time on downsized paper layers, the fused multi-tile grid vs the seed's
stitched Python-loop overlap-add, the Pallas training backward (VJP) vs
the replaced einsum ``_bwd`` and vs XLA conv-transpose autodiff, the NEW
first-class forward-conv rows (stride 1 and 2, 2D and 3D, parity vs the
``lax`` engine asserted at 1e-4), END-TO-END network rows (reduced
discriminator / V-Net-style encoder on the uniform Pallas engine vs the
XLA conv engine, with jaxpr dispatch counters), COMPILED-SCHEDULE rows
(``compile_network`` over a reduced DCGAN generator and a V-Net
encoder+decoder chain — timing plus the schedule report's MXU dispatch
counters), plus the tiling planner's forward/backward decisions for the
real layer geometry (the TPU-relevant structural numbers).

Also emits machine-readable ``BENCH_kernel.json`` at the repo root with
every row, the planner decisions and the compiled per-layer schedules, so
future PRs can diff perf.

    PYTHONPATH=src python benchmarks/kernel_bench.py
"""

import dataclasses as dc
import json
import math
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import (
    EngineConfig,
    UniformEngine,
    compile_network,
    conv_nd,
    init_network_weights,
    networks,
)
from repro.core.engine import default_engine
from repro.core.functional import deconv_nd, deconv_output_shape, deconv_xla
from repro.core.jaxpr_utils import count_prims, pallas_eqns
from repro.core.tiling import plan_uniform_tiles
from repro.kernels.conv import ops as conv_ops
from repro.kernels.deconv import ops as deconv_ops
from repro.kernels.deconv.kernel import vmem_bytes, vmem_bytes_bwd

_JSON_PATH = Path(__file__).resolve().parents[1] / "BENCH_kernel.json"


def _time(fn, *args, repeats=3):
    jax.block_until_ready(fn(*args))   # one warm-up call: compile AND block
    t0 = time.perf_counter()
    for _ in range(repeats):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / repeats * 1e6


def _count_dots(jaxpr):
    return count_prims(jaxpr).get("dot_general", 0)


def run() -> list[str]:
    recs: list[dict] = []

    def rec(name, us, detail=""):
        recs.append({"name": name, "us": round(float(us), 1),
                     "detail": str(detail)})

    rng = np.random.RandomState(0)
    lay2 = dc.replace(networks.benchmark_layers("dcgan")[1], cin=32, cout=16)
    lay3 = dc.replace(networks.benchmark_layers("3d_gan")[1], cin=16, cout=8)
    for name, lay in (("2d", lay2), ("3d", lay3)):
        x = jnp.asarray(rng.randn(1, *lay.in_spatial, lay.cin), jnp.float32)
        w = jnp.asarray(rng.randn(*lay.kernel, lay.cin, lay.cout),
                        jnp.float32)
        for method in ("oom", "xla", "iom_phase", "pallas"):
            f = jax.jit(lambda x, w, m=method: deconv_nd(x, w, lay.stride,
                                                         0, method=m))
            rec(f"kernel_{name}_{method}", _time(f, x, w))

    _split_path_rows(rng, rec)
    _matmul_count_rows(rng, rec)
    _backward_rows(rng, rec)
    _conv_rows(rng, rec)
    _network_rows(rec)
    schedules = _compiled_rows(rng, rec)
    schedules.update(_quantized_rows(rng, rec))
    schedules.update(_graph_rows(rng, rec))
    schedules["dcgan_gen_sharded"] = _sharded_rows(rng, rec)
    runtime = _runtime_rows(rng, rec)
    tuned = _tuned_rows(rng, rec)

    # Planner decisions + VMEM working sets for the REAL layer geometry
    # (forward plan and the backward-budgeted training plan).  The lift
    # matches ops.py: the large dim leads (2D -> [H, 1, W]).
    plans = {}
    for name, lay in (("2d", networks.benchmark_layers("dcgan")[1]),
                      ("3d", networks.benchmark_layers("3d_gan")[1])):
        if lay.rank == 2:
            sp3 = (lay.in_spatial[0], 1, lay.in_spatial[1])
            k3 = (lay.kernel[0], 1, lay.kernel[1])
            s3 = (lay.stride[0], 1, lay.stride[1])
        else:
            sp3, k3, s3 = lay.in_spatial, lay.kernel, lay.stride
        plan = plan_uniform_tiles(sp3, k3, s3, lay.cin, lay.cout)
        tplan = plan_uniform_tiles(sp3, k3, s3, lay.cin, lay.cout,
                                   backward=True)
        vb = vmem_bytes(sp3, k3, s3, plan.block_ci, plan.block_co,
                        dtile=plan.dtile)
        vbb = vmem_bytes_bwd(sp3, k3, s3, tplan.block_ci, tplan.block_co,
                             dtile=tplan.dtile)
        rec(f"kernel_vmem_bytes/{name}", 0, vb)
        rec(f"kernel_blocks/{name}", 0, f"{plan.block_ci}x{plan.block_co}")
        rec(f"kernel_plan/{name}", 0, plan.describe())
        rec(f"kernel_plan_train/{name}", 0, tplan.describe())
        rec(f"kernel_vmem_bytes_bwd/{name}", 0, vbb)
        plans[name] = {"forward": plan.describe(),
                       "train": tplan.describe(),
                       "step_vmem_bytes": vb,
                       "step_vmem_bytes_bwd": vbb}

    _write_json(recs, plans, schedules, runtime, tuned)
    return [f"{r['name']},{r['us']:.0f},{r['detail']}" for r in recs]


def _stitched_baseline(x3, w3, stride3, plan, interpret=True):
    """The seed's pre-fusion path, reconstructed as the benchmark baseline:
    one ``pallas_call`` per leading-dim tile, partial outputs overlap-added
    OUTSIDE the grid via dynamic_update_slice (serial tiles, HBM
    round-trips)."""
    kernel3 = w3.shape[:3]
    out3 = deconv_output_shape(x3.shape[1:4], kernel3, stride3, 0)
    y3 = jnp.zeros((x3.shape[0], *out3, w3.shape[-1]), jnp.float32)
    d, s0 = x3.shape[1], stride3[0]
    for t0 in range(0, d, plan.dtile):
        xt = x3[:, t0:min(t0 + plan.dtile, d)]
        yt = deconv_ops._core_call(xt, w3, stride3, kernel3,
                                   plan.block_ci, plan.block_co, interpret)
        o0 = t0 * s0
        y3 = jax.lax.dynamic_update_slice(
            y3,
            jax.lax.dynamic_slice(
                y3, (0, o0, 0, 0, 0),
                (y3.shape[0], yt.shape[1], *y3.shape[2:]))
            + yt.astype(y3.dtype),
            (0, o0, 0, 0, 0))
    return y3


def _split_path_rows(rng, rec) -> None:
    """Fused 4D grid vs the stitched loop on a forced-split geometry."""
    budget = 96 * 1024
    in_sp, k, s, ci, co = (24, 8, 8), (3, 3, 3), (2, 2, 2), 8, 8
    x = jnp.asarray(rng.randn(1, *in_sp, ci), jnp.float32)
    w = jnp.asarray(rng.randn(*k, ci, co), jnp.float32)
    plan = plan_uniform_tiles(in_sp, k, s, ci, co, vmem_budget=budget)
    assert plan.n_dtiles > 1, plan

    eng = default_engine(method="pallas", interpret=True,
                         max_tile_bytes=budget)
    fused = jax.jit(lambda x, w: deconv_ops._deconv_fwd_impl(
        x, w, None, None, s, 0, 1, 1, "none", 0.2, eng))
    stitched = jax.jit(lambda x, w: _stitched_baseline(x, w, s, plan))
    np.testing.assert_allclose(np.asarray(fused(x, w)),
                               np.asarray(stitched(x, w)),
                               rtol=1e-4, atol=1e-4)
    rec("kernel_split_fused", _time(fused, x, w), plan.describe())
    rec("kernel_split_stitched", _time(stitched, x, w),
        f"tiles{plan.n_dtiles}")


def _matmul_count_rows(rng, rec) -> None:
    """The tap-batching acceptance counter: MXU dispatches per grid step in
    the traced kernels drop from K^d to S^d (forward), and the backward is
    served by pallas_calls."""
    x = jnp.asarray(rng.randn(1, 6, 6, 6, 4), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 3, 4, 4), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x, w: deconv_ops.deconv(x, w, 2, 0))(x, w)
    fwd_dots = _count_dots(pallas_eqns(jaxpr.jaxpr)[0].params["jaxpr"])
    rec("kernel_fwd_matmuls_per_step/3d", 0,
        f"{fwd_dots}(S^3)_was_{math.prod(w.shape[:3])}(K^3)")
    gj = jax.make_jaxpr(jax.grad(
        lambda x, w: jnp.sum(deconv_ops.deconv(x, w, 2, 0)), (0, 1)))(x, w)
    calls = pallas_eqns(gj.jaxpr)
    bwd_dots = [_count_dots(c.params["jaxpr"]) for c in calls[1:]]
    rec("kernel_bwd_pallas_calls", 0,
        f"{len(calls)}calls_dots{'+'.join(map(str, bwd_dots))}")


def _backward_rows(rng, rec) -> None:
    """Training backward on a forced-split 3D geometry, interpret mode.

    Three implementations of the same cotangents: the new Pallas VJP (the
    uniform grid), the replaced einsum ``_bwd`` (K^d full-array f32 einsums
    — XLA fuses these into large multithreaded GEMMs on CPU, so interpret
    mode does NOT beat it at steady state; on TPU those einsums cannot tile
    into VMEM while the Pallas grid does), and XLA conv-transpose autodiff
    (the engine you'd train on WITHOUT the paper's kernel — the Pallas VJP
    beats it even in interpret mode).  Full-gradient rows give the
    end-to-end training-step comparison."""
    budget = 1 << 20
    in_sp, k, s, ci, co = (24, 10, 10), (3, 3, 3), (2, 2, 2), 32, 32
    x = jnp.asarray(rng.randn(1, *in_sp, ci), jnp.float32)
    w = jnp.asarray(rng.randn(*k, ci, co) * 0.1, jnp.float32)
    plan = plan_uniform_tiles(in_sp, k, s, ci, co, vmem_budget=budget,
                              backward=True)
    assert plan.n_dtiles > 1, plan
    y = deconv_ops.deconv(x, w, s, 0, max_tile_bytes=budget)
    dy = jnp.ones_like(y)

    eng = default_engine(method="pallas", interpret=True,
                         max_tile_bytes=budget)
    pallas_vjp = jax.jit(lambda x, w, dy: deconv_ops._bwd(
        s, 0, 1, 1, "none", 0.2, eng, (x, w, None, None, None), dy)[:2])
    einsum_vjp = jax.jit(lambda x, w, dy: deconv_ops._bwd_einsum(
        s, 0, (x, w), dy))
    for a, b in zip(pallas_vjp(x, w, dy), einsum_vjp(x, w, dy)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)

    grad_pallas = jax.jit(jax.grad(
        lambda x, w: jnp.sum(deconv_ops.deconv(x, w, s, 0,
                                               max_tile_bytes=budget)),
        (0, 1)))
    grad_xla = jax.jit(jax.grad(
        lambda x, w: jnp.sum(deconv_xla(x, w, s, 0)), (0, 1)))

    rec("kernel_bwd_split_pallas_vjp", _time(pallas_vjp, x, w, dy),
        plan.describe())
    rec("kernel_bwd_split_einsum", _time(einsum_vjp, x, w, dy),
        "replaced_K^3_einsum__bwd")
    rec("kernel_grad_split_pallas", _time(grad_pallas, x, w),
        "fwd+dx+dw_on_uniform_grid")
    rec("kernel_grad_split_xla_autodiff", _time(grad_xla, x, w),
        "lax_conv_transpose_autodiff")


def _conv_rows(rng, rec) -> None:
    """Forward-conv rows: the promoted strided-conv kernel vs the XLA conv
    engine it displaces — stride 1 and 2, 2D and 3D, parity asserted at
    1e-4 (the PR's acceptance tolerance)."""
    cases = [
        ("2d_s1", (24, 24), (3, 3), 1, 16, 16),
        ("2d_s2", (24, 24), (3, 3), 2, 16, 16),
        ("3d_s1", (10, 10, 10), (3, 3, 3), 1, 8, 8),
        ("3d_s2", (10, 10, 10), (3, 3, 3), 2, 8, 8),
    ]
    for name, in_sp, k, s, ci, co in cases:
        x = jnp.asarray(rng.randn(1, *in_sp, ci), jnp.float32)
        w = jnp.asarray(rng.randn(*k, ci, co), jnp.float32)
        f_pallas = jax.jit(lambda x, w, s=s: conv_ops.conv(x, w, s, 1))
        f_xla = jax.jit(lambda x, w, s=s: conv_nd(x, w, s, 1, method="xla"))
        np.testing.assert_allclose(np.asarray(f_pallas(x, w)),
                                   np.asarray(f_xla(x, w)),
                                   rtol=1e-4, atol=1e-4)
        if len(in_sp) == 2:
            sp3 = (in_sp[0] + 2, 1, in_sp[1] + 2)
            k3 = (k[0], 1, k[1])
            s3 = (s, 1, s)
        else:
            sp3 = tuple(i + 2 for i in in_sp)
            k3, s3 = k, (s,) * 3
        plan = plan_uniform_tiles(sp3, k3, s3, ci, co, mode="conv")
        rec(f"conv_{name}_pallas", _time(f_pallas, x, w), plan.describe())
        rec(f"conv_{name}_xla", _time(f_xla, x, w), "lax_conv_general")


def _network_rows(rec) -> None:
    """End-to-end network rows: whole conv stacks on the uniform Pallas
    engine vs the XLA conv engine, with jaxpr dispatch counters (every
    pallas run must show conv_general_dilated == 0)."""
    from repro.configs import get_config
    from repro.models import dcnn as D
    from repro.sharding.partition import split_params

    key = jax.random.PRNGKey(0)
    rng = np.random.RandomState(0)

    # reduced DCGAN discriminator: 4 strided 2D convs + GAP head
    cfg = get_config("dcgan").reduced()
    disc, _ = split_params(D.init_discriminator(cfg, key))
    layers = D._scaled_layers(cfg)
    x2 = jnp.asarray(rng.randn(2, *layers[-1].out_spatial, layers[-1].cout),
                     jnp.float32)
    # "xla" is a valid method for both engines, so the baseline row name
    # pairs with the encoder rows below (net_*_pallas vs net_*_xla).
    for method in ("pallas", "xla"):
        f = jax.jit(lambda p, x, m=method: D.discriminator_forward(
            p, cfg, x, engine=m))
        counts = count_prims(jax.make_jaxpr(f)(disc, x2).jaxpr, {},
                             into_pallas=False)
        n_pl = counts.get("pallas_call", 0)
        n_cg = counts.get("conv_general_dilated", 0)
        if method == "pallas":
            assert n_cg == 0, counts
        rec(f"net_discriminator_{method}", _time(f, disc, x2),
            f"pallas{n_pl}_convgd{n_cg}")

    # V-Net-style 3D encoder stem: conv s1 -> conv s2 (the workload shape
    # of the full segmenter's hot path, sized for the bench smoke)
    ws = [jnp.asarray(rng.randn(3, 3, 3, 4, 8) * 0.1, jnp.float32),
          jnp.asarray(rng.randn(3, 3, 3, 8, 16) * 0.1, jnp.float32)]
    x3 = jnp.asarray(rng.randn(1, 16, 16, 16, 4), jnp.float32)

    def encoder(x, ws, method):
        h = jax.nn.relu(conv_nd(x, ws[0], 1, 1, method=method))
        return jax.nn.relu(conv_nd(h, ws[1], 2, 1, method=method))

    for method in ("pallas", "xla"):
        f = jax.jit(lambda x, ws, m=method: encoder(x, ws, m))
        counts = count_prims(jax.make_jaxpr(f)(x3, ws).jaxpr, {},
                             into_pallas=False)
        n_pl = counts.get("pallas_call", 0)
        n_cg = counts.get("conv_general_dilated", 0)
        if method == "pallas":
            assert n_cg == 0, counts
        rec(f"net_vnet_encoder_{method}", _time(f, x3, ws),
            f"pallas{n_pl}_convgd{n_cg}")


def _bench_gen_chain():
    """The bench's reduced DCGAN generator chain — ONE definition shared
    with the autotuning sweep driver (``repro.launch.tune``) so the bench
    rows, the tuned rows and the persisted tuned-plan cache all describe
    the same network."""
    from repro.launch.tune import bench_networks

    return bench_networks()["dcgan_gen"]


def _bench_vnet_chain():
    from repro.launch.tune import bench_networks

    return bench_networks()["vnet"]


def _compiled_rows(rng, rec) -> dict:
    """Compiled-schedule rows: ``compile_network`` over a reduced DCGAN
    generator and a V-Net encoder+decoder chain, one configured engine per
    method — timing plus the schedule report's dispatch counters (returned
    for the JSON payload).  Parity vs the XLA engine asserted at 1e-4."""
    key = jax.random.PRNGKey(0)

    schedules = {}
    for name, layers in (("dcgan_gen", _bench_gen_chain()),
                         ("vnet", _bench_vnet_chain())):
        ws = init_network_weights(layers, key)
        x = jnp.asarray(
            rng.randn(1, *layers[0].in_spatial, layers[0].cin) * 0.3,
            jnp.float32)
        outs = {}
        for method in ("pallas", "xla"):
            engine = UniformEngine(method=method)
            fn, report = compile_network(layers, engine)
            f = jax.jit(fn)
            outs[method] = np.asarray(f(ws, x))
            counts = count_prims(jax.make_jaxpr(fn)(ws, x).jaxpr, {},
                                 into_pallas=False)
            n_pl = counts.get("pallas_call", 0)
            n_cg = counts.get("conv_general_dilated", 0)
            if method == "pallas":
                assert n_cg == 0, counts
                assert len(engine.plan_cache) == len(layers)
                schedules[name] = report.to_json()
            rec(f"net_{name}_compiled_{method}", _time(f, ws, x),
                f"pallas{n_pl}_convgd{n_cg}_grid{report.grid_steps}"
                f"_mxu{report.mxu_dispatches}")
        np.testing.assert_allclose(outs["pallas"], outs["xla"],
                                   rtol=1e-4, atol=1e-4)
    return schedules


def _quantized_rows(rng, rec) -> dict:
    """Quantized-engine rows: the SAME bench chains with int8 weights under
    ``Precision(weight_quant="int8")`` — per-channel dequant fused into the
    kernel epilogue.  In-bench acceptance: dispatch counts EQUAL to the f32
    engine, per-step VMEM bytes strictly reduced at every layer, and output
    parity within the documented calibration tolerance (5% of the f32
    output range).  Schedules land in the JSON payload as ``q8_*``."""
    from repro import quant
    from repro.core import Precision

    key = jax.random.PRNGKey(0)
    schedules = {}
    for name, layers in (("dcgan_gen", _bench_gen_chain()),
                         ("vnet", _bench_vnet_chain())):
        ws = init_network_weights(layers, key)
        wq = quant.quantize_weights(ws, Precision(weight_quant="int8"))
        x = jnp.asarray(
            rng.randn(1, *layers[0].in_spatial, layers[0].cin) * 0.3,
            jnp.float32)
        f32_fn, f32_rep = compile_network(layers,
                                          UniformEngine(method="pallas"))
        y_f32 = np.asarray(jax.jit(f32_fn)(ws, x))
        tol = 0.05 * float(np.max(np.abs(y_f32))) + 1e-6
        outs = {}
        for method in ("pallas", "xla"):
            eng = UniformEngine(EngineConfig(
                method=method, precision=Precision(weight_quant="int8")))
            fn, report = compile_network(layers, eng)
            f = jax.jit(fn)
            outs[method] = np.asarray(f(wq, x))
            counts = count_prims(jax.make_jaxpr(fn)(wq, x).jaxpr, {},
                                 into_pallas=False)
            n_pl = counts.get("pallas_call", 0)
            if method == "pallas":
                assert counts.get("conv_general_dilated", 0) == 0, counts
                # acceptance: int8 weights change the working set, NOT the
                # launch structure — dispatch counts equal the f32 engine,
                # per-step VMEM bytes never grow and drop overall (a thin
                # weight block pads to the same tiles at either width)
                assert report.mxu_dispatches == f32_rep.mxu_dispatches
                assert report.grid_steps == f32_rep.grid_steps
                for rq, rf in zip(report.layers, f32_rep.layers):
                    assert rq.vmem_bytes <= rf.vmem_bytes, (rq, rf)
                assert (sum(r.vmem_bytes for r in report.layers)
                        < sum(r.vmem_bytes for r in f32_rep.layers))
                schedules[f"q8_{name}"] = report.to_json()
            err = float(np.max(np.abs(outs[method] - y_f32)))
            assert err <= tol, (name, method, err, tol)
            rec(f"q8_{name}_{method}", _time(f, wq, x),
                f"pallas{n_pl}_grid{report.grid_steps}"
                f"_mxu{report.mxu_dispatches}_maxerr{err:.4f}")
        np.testing.assert_allclose(outs["pallas"], outs["xla"],
                                   rtol=1e-3, atol=1e-3)
    return schedules


def _bench_graphs() -> dict:
    """The bench's DAG networks — the generator chain with FUSED epilogues
    (bias+relu, tanh head) and the full V-Net graph with its skip concats —
    shared by the graph rows and the runtime-utilization rows so they
    measure the same compiled schedules."""
    gen = _bench_gen_chain()
    gen = [dc.replace(l, epilogue=networks.Epilogue(
               bias=True,
               activation="tanh" if i == len(gen) - 1 else "relu"))
           for i, l in enumerate(gen)]
    return {
        "dcgan_gen_graph": networks.chain_graph(gen),
        "vnet_full_graph": networks.vnet_graph(
            in_spatial=(8, 8, 8), chans=(2, 4, 8), cin=1, num_classes=2),
    }


def _graph_rows(rng, rec) -> dict:
    """DAG-schedule rows: ``compile_network`` over the bench graphs
    (``_bench_graphs``) — per-method timing, jaxpr dispatch counters (the
    pallas runs must trace zero conv_general_dilated AND zero
    outside-kernel activations), parity at 1e-4, schedules in the JSON
    payload."""
    key = jax.random.PRNGKey(0)

    graphs = _bench_graphs()
    schedules = {}
    for name, graph in graphs.items():
        ws = init_network_weights(graph, key)
        sp, ci = graph.in_shape
        x = jnp.asarray(rng.randn(1, *sp, ci) * 0.3, jnp.float32)
        outs = {}
        for method in ("pallas", "xla"):
            fn, report = compile_network(graph, UniformEngine(method=method))
            f = jax.jit(fn)
            outs[method] = np.asarray(f(ws, x))
            counts = count_prims(jax.make_jaxpr(fn)(ws, x).jaxpr, {},
                                 into_pallas=False)
            n_pl = counts.get("pallas_call", 0)
            n_cg = counts.get("conv_general_dilated", 0)
            if method == "pallas":
                assert n_cg == 0, counts
                assert counts.get("tanh", 0) == 0, counts   # fused epilogue
                assert counts.get("max", 0) == 0, counts
                schedules[name] = report.to_json()
            rec(f"net_{name}_{method}", _time(f, ws, x),
                f"pallas{n_pl}_convgd{n_cg}_grid{report.grid_steps}"
                f"_mxu{report.mxu_dispatches}")
        np.testing.assert_allclose(outs["pallas"], outs["xla"],
                                   rtol=1e-4, atol=1e-4)
    return schedules


def _sharded_rows(rng, rec) -> dict:
    """Mesh-aware compiled schedule: the same reduced DCGAN generator chain
    through a ``shard_map``-wrapped ``compile_network`` on the host mesh
    (a (1, 1) mesh on single-device CI — still the full shard_map path;
    more under ``--xla_force_host_platform_device_count``).  Parity vs the
    unsharded engine asserted at 1e-4; the schedule (with its per-device
    plans and collective accounting) lands in the JSON payload."""
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    dp = mesh.shape["data"]
    gen = _bench_gen_chain()
    ws = init_network_weights(gen, jax.random.PRNGKey(0))
    x = jnp.asarray(rng.randn(dp, *gen[0].in_spatial, gen[0].cin) * 0.3,
                    jnp.float32)
    base_fn, _ = compile_network(gen, UniformEngine(method="pallas"))
    sh_fn, report = compile_network(
        gen, UniformEngine(EngineConfig(method="pallas", mesh=mesh)),
        batch=dp)
    f = jax.jit(sh_fn)
    np.testing.assert_allclose(np.asarray(f(ws, x)),
                               np.asarray(base_fn(ws, x)),
                               rtol=1e-4, atol=1e-4)
    rec("net_dcgan_gen_sharded_pallas", _time(f, ws, x),
        f"dp{report.data_parallel}_coll{report.collective_bytes}B")
    return report.to_json()


def _runtime_rows(rng, rec) -> dict:
    """Measured-vs-modeled utilization rows — paper Fig. 6 from live runs.

    ``obs.measure_network`` executes every node of the compiled generator
    graph and the full V-Net graph on BOTH engines, joining host wall time
    against the schedule's modeled valid MACs and a roofline peak
    (``REPRO_PEAK_GFLOPS`` or the calibration probe).  The per-layer
    tables land under the JSON payload's ``runtime`` key; the summary
    rows are trajectory-anchored info-only (absolute utilization is a
    machine property, not a regression signal).

    Also times the telemetry-instrumented dispatch path against the bare
    jitted apply on the same graphs — the host-side overhead the spine
    adds per eager dispatch (acceptance: <5% of the graph row's wall).
    """
    from repro import obs

    key = jax.random.PRNGKey(0)
    graphs = _bench_graphs()
    short = {"dcgan_gen_graph": "dcgan_gen", "vnet_full_graph": "vnet"}
    runtime = {}
    for gname, graph in graphs.items():
        for method in ("pallas", "xla"):
            rpt = obs.measure_network(graph, UniformEngine(method=method),
                                      name=gname, repeats=3)
            runtime[f"{short[gname]}_{method}"] = rpt.to_json()
            rec(f"util_{short[gname]}_{method}", rpt.net_wall_s * 1e6,
                f"util{100 * rpt.utilization:.3f}%_"
                f"{rpt.achieved_gflops:.2f}GF/s_"
                f"peak{rpt.peak_gflops:.0f}_macs{rpt.total_macs}")

        # telemetry overhead: the SAME jitted callable, bare vs wrapped by
        # the engine's host-side dispatch timer (eager path — under jit
        # the wrapper is a pure pass-through and the overhead is zero)
        tel = obs.Telemetry.create()
        ws = init_network_weights(graph, key)
        sp, ci = graph.in_shape
        x = jnp.asarray(rng.randn(1, *sp, ci) * 0.3, jnp.float32)
        bare_fn, _ = compile_network(graph, UniformEngine(method="pallas"))
        f_bare = jax.jit(bare_fn)
        f_inst = obs.instrument_apply(f_bare, tel, f"bench:{gname}")
        t_bare = _time(f_bare, ws, x, repeats=5)
        t_inst = _time(f_inst, ws, x, repeats=5)
        overhead_pct = (t_inst - t_bare) / t_bare * 100
        rec(f"telemetry_overhead_{short[gname]}_pallas", t_inst,
            f"bare{t_bare:.0f}us_overhead{overhead_pct:+.2f}%")
    return runtime


def _tuned_rows(rng, rec) -> dict:
    """Autotuned-schedule rows: ``repro.tune`` searches the tile-plan
    space for the SAME bench networks (model-ranked, top-1 measured live
    against the first-fit heuristic), then the tuned cache drives a fresh
    engine through ``EngineConfig(tuned_plans=...)``.  Emits
    ``tuned_{name}_pallas`` (gated by the trajectory) with its
    ``tuned_{name}_xla`` sibling for machine-normalization, asserts the
    tuned engine planned with ZERO heuristic fallbacks and parity vs XLA
    at 1e-4.  The per-geometry winners land in the JSON payload."""
    from repro import tune as _tune
    from repro.launch.tune import bench_networks

    key = jax.random.PRNGKey(0)
    nets = bench_networks()
    cache = _tune.TunedPlanCache()
    tuned = {"entries": {}, "networks": {}}
    for name, layers in nets.items():
        cache, results = _tune.tune_network(
            layers, trials=24, measure_topk=1, repeats=2, seed=0,
            cache=cache)
        tuned["networks"][name] = [r.to_json() for r in results]

    for name, layers in nets.items():
        ws = init_network_weights(layers, key)
        x = jnp.asarray(
            rng.randn(1, *layers[0].in_spatial, layers[0].cin) * 0.3,
            jnp.float32)
        outs = {}
        for method in ("pallas", "xla"):
            eng = UniformEngine(EngineConfig(
                method=method,
                tuned_plans=cache if method == "pallas" else None))
            fn, report = compile_network(layers, eng)
            f = jax.jit(fn)
            outs[method] = np.asarray(f(ws, x))
            detail = f"grid{report.grid_steps}_mxu{report.mxu_dispatches}"
            if method == "pallas":
                assert eng.plan_sources["heuristic"] == 0, (
                    "tuned bench engine fell back to the heuristic: "
                    f"{eng.plan_sources}")
                detail += f"_tunedhits{eng.plan_sources['tuned']}"
            rec(f"tuned_{name}_{method}", _time(f, ws, x), detail)
        np.testing.assert_allclose(outs["pallas"], outs["xla"],
                                   rtol=1e-4, atol=1e-4)
    tuned["entries"] = {k: e.to_json() for k, e in
                        sorted(cache.entries.items())}
    return tuned


def _write_json(recs, plans, schedules, runtime, tuned) -> None:
    payload = {
        "bench": "kernel",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "interpret": True,
        "rows": recs,
        "plans": plans,
        "schedules": schedules,
        "runtime": runtime,
        "tuned": tuned,
    }
    _JSON_PATH.write_text(json.dumps(payload, indent=1) + "\n")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for row in run():
        print(row)
    print(f"wrote {_JSON_PATH}")
