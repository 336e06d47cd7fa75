"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch dcgan --steps 200
    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --reduced --steps 100 --batch 8 --seq 128

Real-cluster notes (1000+ nodes): this same entry point runs under
``jax.distributed.initialize()`` (env-driven); the XLA flags below enable
the latency-hiding scheduler so collectives overlap compute on TPU.  On
this CPU container it trains reduced configs end-to-end.
"""

from __future__ import annotations

import argparse
import os

TPU_PERF_FLAGS = " ".join([
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_megacore_fusion_allow_ags=true",
    "--xla_enable_async_collective_permute=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-dir", default="checkpoints")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--deconv-method", default="iom_phase")
    ap.add_argument("--dp", action="store_true",
                    help="dcnn archs: explicit data-parallel shard_map "
                         "trainer (int8-compressed gradient all-reduce)")
    ap.add_argument("--no-dp-compress", action="store_true",
                    help="with --dp: plain f32 gradient all-reduce")
    ap.add_argument("--telemetry", metavar="OUT_JSONL", default=None,
                    help="record step-time/grads-bytes/collective-bytes "
                         "metrics + spans to this JSONL event log")
    args = ap.parse_args()

    if os.environ.get("TPU_PERF", "0") == "1":
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                                   + TPU_PERF_FLAGS)

    import jax
    import jax.numpy as jnp
    from repro import obs
    from repro.configs import get_config
    from repro.data import DcnnBatches, TokenBatches, VolumeBatches
    from repro.launch import steps as ST
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models import dcnn as D
    from repro.optim import AdamWConfig, adamw_init
    from repro.runtime import Trainer, TrainLoopConfig
    from repro.runtime.dp_trainer import record_dp_metrics

    print(f"compile cache: {enable_compile_cache()}")
    telemetry = (obs.Telemetry.create(jsonl_path=args.telemetry)
                 if args.telemetry else None)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh(model=args.model_parallel)
    opt = AdamWConfig(lr=args.lr, state_bits=cfg.opt_state_bits)

    use_dp = args.dp and cfg.family == "dcnn"
    n_data = mesh.shape["data"]
    if use_dp:
        cfg = ST.round_batch_to_mesh(cfg, n_data)
        # the dp opt state carries the error-feedback residual: keep its
        # checkpoints apart from non-dp runs (different tree structure)
        args.checkpoint_dir += "-dp"

    with mesh:
        params, logical = ST.real_params(cfg, jax.random.PRNGKey(0))
        if cfg.family == "dcnn":
            compress = not args.no_dp_compress
            if cfg.dcnn == "v_net":
                data = VolumeBatches(cfg.dcnn_batch, D._vnet_spatial(cfg))
                if use_dp:
                    dp_step = ST.make_dp_vnet_train_step(
                        cfg, opt, mesh, engine=args.deconv_method,
                        compress=compress)
                    step_fn, err = ST.fold_dp_step(dp_step, n_data, params)
                    opt_state = (adamw_init(params, opt), err)
                else:
                    step_fn = ST.make_vnet_train_step(
                        cfg, opt, engine=args.deconv_method)
                    opt_state = adamw_init(params, opt)
            else:
                layers = D._scaled_layers(cfg)
                data = DcnnBatches(cfg.dcnn_batch, cfg.dcnn_z,
                                   (*layers[-1].out_spatial,
                                    layers[-1].cout))
                if use_dp:
                    dp_step = ST.make_dp_gan_train_step(
                        cfg, opt, mesh, engine=args.deconv_method,
                        compress=compress)
                    step_fn, err = ST.fold_dp_step(dp_step, n_data, params)
                    opt_state = ((adamw_init(params["gen"], opt),
                                  adamw_init(params["disc"], opt)), err)
                else:
                    step_fn = ST.make_gan_train_step(
                        cfg, opt, engine=args.deconv_method)
                    opt_state = (adamw_init(params["gen"], opt),
                                 adamw_init(params["disc"], opt))
        else:
            def extra_fn(step, b, s):
                extra = {}
                if cfg.family == "encdec":
                    extra["enc_embeds"] = jnp.zeros(
                        (b, cfg.enc_seq, cfg.d_model), jnp.float32)
                if cfg.mrope:
                    extra["mrope_positions"] = jnp.broadcast_to(
                        jnp.arange(s)[None, None], (3, b, s)).astype(
                        jnp.int32)
                return extra
            data = TokenBatches(cfg.vocab, args.batch, args.seq,
                                extra_fn=extra_fn)
            step_fn = ST.make_train_step(cfg, opt)
            opt_state = adamw_init(params, opt)

        if telemetry is not None and use_dp:
            # reduce_grads runs traced, so the wire accounting is static —
            # computed from the param tree, recorded as gauges
            acct = record_dp_metrics(telemetry, params,
                                     compress=not args.no_dp_compress,
                                     n_data=n_data)
            print(f"dp wire: grads={acct['grads_bytes']}B collective="
                  f"{acct['collective_bytes']}B "
                  f"({acct['compress_ratio']:.2f}x compression)")

        # the dp steps come back pre-jitted from dp_trainer.make_dp_step
        jitted = (step_fn if use_dp
                  else jax.jit(step_fn, donate_argnums=(0, 1)))
        trainer = Trainer(jitted, params, opt_state, data,
                          TrainLoopConfig(
                              total_steps=args.steps,
                              checkpoint_every=args.checkpoint_every,
                              checkpoint_dir=args.checkpoint_dir),
                          telemetry=telemetry)
        if args.resume:
            resumed = trainer.maybe_resume()
            print(f"resume: {'ok, step=' + str(trainer.step) if resumed else 'no checkpoint found'}")
        trainer.run()
        print(f"finished at step {trainer.step}; "
              f"stragglers={trainer.straggler_events}")
        if telemetry is not None:
            step_snap = telemetry.histogram("train_step_seconds").snapshot()
            if step_snap["count"]:
                print(f"step time p50={step_snap['p50'] * 1e3:.1f}ms "
                      f"p99={step_snap['p99'] * 1e3:.1f}ms over "
                      f"{step_snap['count']} steps")
            telemetry.flush_metrics()
            telemetry.close()
            print(f"telemetry written to {args.telemetry}")


if __name__ == "__main__":
    main()
