"""Distributed-optimisation microbench: int8 gradient all-reduce.

Wire bytes: f32 all-reduce vs int8 payload (4x reduction), plus the
convergence check (error feedback removes quantisation bias).  Runs in the
calling process, data-parallel over every device it has (one on a
one-chip host; force more CPU devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``)."""

import numpy as np


def run() -> list[str]:
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_host_mesh
    from repro.optim import AdamWConfig, adamw_init
    from repro.runtime.dp_trainer import make_dp_train_step, init_error_state

    mesh = make_host_mesh(model=1)
    n_data = mesh.shape["data"]
    rng = np.random.RandomState(0)
    A = jnp.asarray(rng.randn(64, 32), jnp.float32)
    t = jnp.asarray(rng.randn(32), jnp.float32)
    y = A @ t

    def loss_fn(params, batch):
        xb, yb = batch
        return jnp.mean((xb @ params["w"] - yb) ** 2)

    losses = {}
    for compress in (False, True):
        params = {"w": jnp.zeros(32)}
        opt = AdamWConfig(lr=0.05, weight_decay=0.0)
        s = adamw_init(params, opt)
        err = init_error_state(params, n_data)
        step = make_dp_train_step(loss_fn, opt, mesh, compress=compress)
        for _ in range(120):
            params, s, err, loss = step(params, s, err, (A, y))
        losses[compress] = float(loss)
    n_params = 32
    wire_f32, wire_int8 = n_params * 4, n_params * 1 + 4
    return [f"compress_loss_f32,0,{losses[False]:.2e}",
            f"compress_loss_int8_ef,0,{losses[True]:.2e}",
            f"compress_wire_ratio,0,{wire_f32 / wire_int8:.2f}",
            f"compress_data_parallel,0,{n_data}"]
