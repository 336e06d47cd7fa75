#!/usr/bin/env python3
"""Smoke test of the uniform Pallas engine on a TPU: serving and training.

    python chip_smoke.py                 # one chip: serving + GAN training
    python chip_smoke.py --chips 4       # four-chip host: data-parallel GAN
                                         # step against the one-device step

Rehearsals without a chip (reduced widths, Pallas interpret mode — the only
way this script runs off a TPU):

    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --rehearse --chips 4

One chip, two phases, both through the entry points a user calls:

1. serving — ``DcnnServer`` with a Pallas primary
   (``EngineConfig(method="pallas", interpret=False, strict_vmem=True)``)
   answers requests for the DCGAN generator (4x4x1024 -> 64x64x3) and V-Net
   (128x128x64 volume, channels 16-256).  Every output is checked against
   the XLA engine on the same device, and every bucket must have been
   served by Pallas with no fallback, retry or quarantine.
2. training — ``runtime.Trainer`` drives ``launch.steps.make_gan_train_step``
   for ``get_config("dcgan")`` (published widths, batch 64, discriminator
   included) on the same Pallas engine, as ``launch/train.py`` does.  The
   first batch's losses and gradients are checked against the XLA engine
   and every step's losses must be finite.

``--chips 4`` runs only the data-parallel GAN step
(``make_dp_gan_train_step`` on ``make_host_mesh()``) with the plain f32 and
the int8-compressed all-reduce, against the one-device step on the same
global batch.

Matmuls run at full f32 precision (``jax.default_matmul_precision
("highest")``) so the Pallas kernels and the XLA reference compute the same
function.
Weights and data are random, made from ``--seed``.  The last line of
standard output is ``{"ok": true, "device": {...}}`` and is printed only
when every phase passed; any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# -- the bounds every check is held to ---------------------------------------
# served output vs the XLA engine: max |pallas - xla| / max |xla|
SERVE_REL_ERR = 1e-3
# first-batch GAN losses vs the XLA engine (absolute; the losses are ~0.7)
LOSS_ABS_ERR = 1e-4
# first-batch gradients vs the XLA engine: per leaf max |d| / max |ref|
GRAD_REL_ERR = 2e-3
# dp step vs one-device step: the share of parameters whose updates differ
# by more than a tenth of the learning rate (Adam's first update is
# ~lr*sign(g), so only gradients at rounding level may flip)
DP_PARAM_FLIP_SHARE = 1e-3
VMEM_BUDGET = 48 << 20   # V-Net's full-resolution planes need ~47 MiB
TRAIN_STEPS = 3


@dataclasses.dataclass(frozen=True)
class Widths:
    dcgan_chans: tuple[int, ...]
    vnet_chans: tuple[int, ...]
    vnet_spatial: tuple[int, ...]
    dcgan_requests: int
    vnet_requests: int
    max_batch: int
    reduced_train: bool


# published widths (networks.dcgan(), networks.vnet_graph defaults)
FULL = Widths(dcgan_chans=(1024, 512, 256, 128, 3),
              vnet_chans=(16, 32, 64, 128, 256), vnet_spatial=(128, 128, 64),
              dcgan_requests=8, vnet_requests=4, max_batch=8,
              reduced_train=False)
# CPU rehearsal: same graphs and code path, channels and volumes cut
REHEARSAL = Widths(dcgan_chans=(16, 8, 4, 3), vnet_chans=(2, 4, 8),
                   vnet_spatial=(8, 8, 8), dcgan_requests=2,
                   vnet_requests=2, max_batch=2, reduced_train=True)


def _engines(interpret: bool):
    from repro.core.engine import EngineConfig, UniformEngine
    pallas = UniformEngine(EngineConfig(
        method="pallas", interpret=interpret, strict_vmem=True,
        max_tile_bytes=VMEM_BUDGET))
    return pallas, UniformEngine(EngineConfig(method="xla"))


def _rel_err(got, ref) -> float:
    import numpy as np
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if not np.all(np.isfinite(got)):
        return math.inf
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)),
                                                 1e-30))


def _train_setup(w: Widths, seed: int, n_data: int = 1):
    import jax
    from repro.configs import get_config
    from repro.launch import steps as ST
    from repro.models import dcnn as D
    from repro.optim import AdamWConfig

    cfg = get_config("dcgan")
    if w.reduced_train:
        cfg = cfg.reduced()
    cfg = ST.round_batch_to_mesh(cfg, n_data)
    opt = AdamWConfig(lr=3e-4, state_bits=cfg.opt_state_bits)
    params, _ = ST.real_params(cfg, jax.random.PRNGKey(seed))
    last = D._scaled_layers(cfg)[-1]
    return cfg, opt, params, (*last.out_spatial, last.cout)


def _opt_init(params, opt):
    from repro.optim import adamw_init
    return (adamw_init(params["gen"], opt), adamw_init(params["disc"], opt))


# -- phase 1: serving ---------------------------------------------------------

def serve_phase(w: Widths, pallas, xla, seed: int) -> dict:
    """Serve both models through ``DcnnServer`` and check every output."""
    import jax
    import numpy as np
    from repro.core.engine import compile_network
    from repro.runtime.dcnn_server import (
        DcnnServer, ServeRequest, dcgan_gen_spec, vnet_spec)

    specs = [dcgan_gen_spec(jax.random.PRNGKey(seed), chans=w.dcgan_chans),
             vnet_spec(jax.random.PRNGKey(seed + 1), chans=w.vnet_chans,
                       base_spatial=w.vnet_spatial)]
    server = DcnnServer(specs, engines={"pallas": pallas, "xla": xla},
                        max_batch=w.max_batch)
    rng = np.random.default_rng(seed)
    sent: dict[str, dict[int, np.ndarray]] = {"dcgan_gen": {}, "vnet": {}}
    for model, n in (("dcgan_gen", w.dcgan_requests),
                     ("vnet", w.vnet_requests)):
        spec = server.specs[model]
        for _ in range(n):
            x = rng.standard_normal((*spec.base_spatial, spec.cin),
                                    dtype=np.float32)
            sent[model][server.submit(ServeRequest(model, x))] = x
    t0 = time.perf_counter()
    results = server.drain()
    wall = time.perf_counter() - t0
    bad = [r for r in results if not r.ok or r.engine != "pallas"]
    if bad or len(results) != sum(map(len, sent.values())):
        raise RuntimeError(f"serving: {len(results)} results, not all "
                           f"served ok by pallas: "
                           f"{[(r.id, r.code, r.engine) for r in bad]}")
    stats = server.stats()
    for key, b in stats["buckets"].items():
        if b["engine"] != "pallas" or b["fallbacks"] or b["degraded"]:
            raise RuntimeError(f"serving: bucket {key} left the Pallas "
                               f"engine: {b}")
    for c in ("fallbacks", "retries", "quarantined"):
        if stats[c]:
            raise RuntimeError(f"serving: {c}={stats[c]}: {stats}")

    errs = {}
    by_id = {r.id: r for r in results}
    for model, reqs in sent.items():
        spec = server.specs[model]
        ids = sorted(reqs)
        xb = np.stack([reqs[i] for i in ids])
        graph = spec.graph_for(spec.base_spatial)
        apply_ref, _ = compile_network(graph, xla, batch=len(ids))
        ws = jax.tree_util.tree_map(jax.numpy.asarray, dict(spec.weights))
        ref = np.asarray(jax.jit(apply_ref)(ws, xb))
        got = np.stack([by_id[i].output for i in ids])
        errs[model] = _rel_err(got, ref)
        print(f"serve {model}: {len(ids)} requests {tuple(xb.shape)} -> "
              f"{tuple(got.shape)} on pallas, max rel err vs xla "
              f"{errs[model]:.3e} (bound {SERVE_REL_ERR:.0e})", flush=True)
        if not errs[model] <= SERVE_REL_ERR:
            raise RuntimeError(f"serving: {model} output off the XLA "
                               f"reference by {errs[model]:.3e}")
    summary = {k: stats[k] for k in ("completed", "fallbacks", "retries",
                                     "quarantined", "shed", "expired")}
    summary["buckets"] = {k: {"engine": b["engine"], "batches": b["batches"]}
                          for k, b in stats["buckets"].items()}
    print(f"serve stats: {json.dumps(summary)}")
    print(f"serve wall seconds (host clock, compiles included): {wall:.2f}",
          flush=True)
    return errs


# -- phase 2: training --------------------------------------------------------

def _loss_and_grads(cfg, engine):
    import jax
    from repro.models import dcnn as D

    def fn(params, batch):
        def g_loss(gp):
            return D.gan_losses(gp, params["disc"], cfg, batch["z"],
                                batch["real"], engine)[0]

        def d_loss(dp):
            return D.gan_losses(params["gen"], dp, cfg, batch["z"],
                                batch["real"], engine)[1]
        gl, gg = jax.value_and_grad(g_loss)(params["gen"])
        dl, dg = jax.value_and_grad(d_loss)(params["disc"])
        return {"g_loss": gl, "d_loss": dl}, {"gen": gg, "disc": dg}
    return jax.jit(fn)


def train_phase(w: Widths, pallas, xla, seed: int) -> list[dict]:
    """Three Trainer steps of the Pallas GAN step, first batch checked."""
    import jax
    import numpy as np
    from repro.data import DcnnBatches
    from repro.launch import steps as ST
    from repro.runtime import Trainer, TrainLoopConfig

    cfg, opt, params, out_shape = _train_setup(w, seed)
    data = DcnnBatches(cfg.dcnn_batch, cfg.dcnn_z, out_shape, seed=seed)
    first = data.make_batch(0)           # what the trainer's step 1 sees

    t0 = time.perf_counter()
    got_l, got_g = _loss_and_grads(cfg, pallas)(params, first)
    jax.block_until_ready(got_g)
    t_grad = time.perf_counter() - t0
    ref_l, ref_g = _loss_and_grads(cfg, xla)(params, first)
    grad_err, worst = max(
        (_rel_err(a, b), jax.tree_util.keystr(path)) for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(got_g),
            jax.tree_util.tree_leaves(ref_g)))
    loss_err = max(abs(float(got_l[k]) - float(ref_l[k])) for k in ref_l)
    print(f"train batch {cfg.dcnn_batch} first-batch check vs xla: loss "
          f"abs err {loss_err:.3e} (bound {LOSS_ABS_ERR:.0e}), grad rel err "
          f"{grad_err:.3e} at {worst} (bound {GRAD_REL_ERR:.0e}); pallas "
          f"grads {t_grad:.2f}s incl. compile", flush=True)
    if not (loss_err <= LOSS_ABS_ERR and grad_err <= GRAD_REL_ERR):
        raise RuntimeError("training: first-batch losses/gradients off the "
                           "XLA reference")

    ckpt = ROOT / ".chip_smoke" / "checkpoints"
    shutil.rmtree(ckpt, ignore_errors=True)
    step_fn = ST.make_gan_train_step(cfg, opt, engine=pallas)
    trainer = Trainer(jax.jit(step_fn, donate_argnums=(0, 1)), params,
                      _opt_init(params, opt), data,
                      TrainLoopConfig(total_steps=TRAIN_STEPS,
                                      checkpoint_every=TRAIN_STEPS,
                                      log_every=1,
                                      checkpoint_dir=str(ckpt)))
    try:
        trainer.run()
    finally:
        shutil.rmtree(ckpt.parent, ignore_errors=True)
    log = trainer.metrics_log
    if trainer.step != TRAIN_STEPS or len(log) != TRAIN_STEPS:
        raise RuntimeError(f"training: ran {trainer.step} steps, logged "
                           f"{len(log)}")
    if not all(np.isfinite(r[k]) for r in log for k in ("g_loss", "d_loss")):
        raise RuntimeError(f"training: non-finite loss {log}")
    step1_err = max(abs(log[0][k] - float(ref_l[k])) for k in ref_l)
    print(f"train step 1 losses vs xla first batch: abs err "
          f"{step1_err:.3e} (bound {LOSS_ABS_ERR:.0e})")
    if not step1_err <= LOSS_ABS_ERR:
        raise RuntimeError("training: step 1 losses off the XLA reference")
    print("train steps (host wall seconds; step 1 includes compile): "
          + ", ".join(f"{r['step']}: g={r['g_loss']:.6f} d={r['d_loss']:.6f}"
                      f" {r['dt_s']:.2f}s" for r in log), flush=True)
    return log


# -- --chips 4: the data-parallel GAN step -----------------------------------

def dp_phase(w: Widths, pallas, seed: int, chips: int) -> dict:
    """dp step (plain f32 and int8-compressed all-reduce) against the
    one-device step on the same global batch."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.data import DcnnBatches
    from repro.launch import steps as ST
    from repro.launch.mesh import make_host_mesh
    from repro.runtime import dp_trainer as DP

    mesh = make_host_mesh()
    n = mesh.shape["data"]
    if n != chips:
        raise RuntimeError(f"dp: {n} devices on the data axis, expected "
                           f"{chips}")
    cfg, opt, params, out_shape = _train_setup(w, seed, n)
    batch = DcnnBatches(cfg.dcnn_batch, cfg.dcnn_z, out_shape, seed=seed,
                        prefetch=False).make_batch(0)

    one = jax.devices()[0]
    on_one = jax.device_put((params, _opt_init(params, opt), batch), one)
    t0 = time.perf_counter()
    ref_p, _, ref_m = jax.jit(ST.make_gan_train_step(
        cfg, opt, engine=pallas))(*on_one)
    ref_p = jax.device_get(ref_p)
    print(f"dp reference: one-device step batch {cfg.dcnn_batch}: "
          f"g={float(ref_m['g_loss']):.6f} d={float(ref_m['d_loss']):.6f} "
          f"({time.perf_counter() - t0:.2f}s incl. compile)", flush=True)

    rep, dat = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    out = {}
    for compress in (False, True):
        step = ST.make_dp_gan_train_step(cfg, opt, mesh, engine=pallas,
                                         compress=compress)
        args = (jax.device_put(params, rep),
                jax.device_put(_opt_init(params, opt), rep),
                jax.device_put(DP.init_error_state(params, n), dat),
                jax.device_put(batch, dat))
        for leaf in jax.tree_util.tree_leaves(args[3]):
            starts = {s.index[0].start for s in leaf.addressable_shards}
            rows = {s.data.shape[0] for s in leaf.addressable_shards}
            if len(starts) != n or rows != {leaf.shape[0] // n}:
                raise RuntimeError(f"dp: batch leaf {leaf.shape} is not "
                                   f"split into {n} distinct shards")
        t0 = time.perf_counter()
        compiled = step.lower(*args).compile()
        if "all-reduce" not in compiled.as_text():
            raise RuntimeError("dp: no all-reduce in the compiled step")
        new_p, _, _, m = compiled(*args)
        g, d = float(m["g_loss"]), float(m["d_loss"])
        wall = time.perf_counter() - t0
        loss_err = max(abs(g - float(ref_m["g_loss"])),
                       abs(d - float(ref_m["d_loss"])))
        tag = "int8" if compress else "f32"
        if not (math.isfinite(g) and math.isfinite(d)):
            raise RuntimeError(f"dp {tag}: non-finite loss")
        line = (f"dp {tag} all-reduce on {n} devices: g={g:.6f} d={d:.6f}, "
                f"loss abs err vs one device {loss_err:.3e}")
        if not compress:
            deltas = [np.abs(np.asarray(a, np.float64) - np.asarray(b))
                      for a, b in zip(jax.tree_util.tree_leaves(
                          jax.device_get(new_p)),
                          jax.tree_util.tree_leaves(ref_p))]
            flipped = sum(int(np.sum(dl > 0.1 * opt.lr)) for dl in deltas)
            share = flipped / sum(dl.size for dl in deltas)
            line += (f", params off by >lr/10: {share:.2e} of elements "
                     f"(bound {DP_PARAM_FLIP_SHARE:.0e})")
            if not (loss_err <= LOSS_ABS_ERR
                    and share <= DP_PARAM_FLIP_SHARE):
                raise RuntimeError(f"dp f32 step off the one-device step: "
                                   f"{line}")
        print(f"{line} ({wall:.2f}s incl. compile)", flush=True)
        out[tag] = {"g_loss": g, "d_loss": d, "loss_err": loss_err}
    return out


# -- entry point --------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel GAN step on a "
                         "four-chip host")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at reduced widths in Pallas "
                         "interpret mode")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if not args.rehearse and backend != "tpu":
        print(f"chip_smoke: JAX found no TPU (default backend: {backend}); "
              f"run it on a TPU host, or pass --rehearse for the CPU "
              f"rehearsal", file=sys.stderr)
        return 2
    if not args.rehearse:
        from repro.launch.compile_cache import enable_compile_cache
        print(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()
    print(f"devices: {len(devices)} x {devices[0].platform} "
          f"{devices[0].device_kind}; jax {jax.__version__}", flush=True)

    w = REHEARSAL if args.rehearse else FULL
    pallas, xla = _engines(interpret=args.rehearse)
    with jax.default_matmul_precision("highest"):
        if args.chips == 4:
            dp_phase(w, pallas, args.seed, args.chips)
        else:
            serve_phase(w, pallas, xla, args.seed)
            train_phase(w, pallas, xla, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
