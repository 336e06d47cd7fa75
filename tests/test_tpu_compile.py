"""The engine's Pallas kernels compile for a TPU v5e (no chip needed).

Each case lowers an engine op with ``interpret=False`` against a described
``v5e:2x2`` topology and compiles it with the TPU compiler: Mosaic refuses
here whatever it would refuse on the chip (unaligned reshapes, strided
value slices, blocks over the scoped VMEM limit).  The layers are the
published widths: a DCGAN generator deconv (2D), a V-Net encoder conv and
decoder deconv (3D), and V-Net's thin-channel 1x1x1 head.  ``fwd``
compiles the forward kernel, ``grad`` the dx and dw kernels of the op's
custom VJP (the gradient of a sum needs no forward output, so XLA drops
the forward kernel there).

The topology is described inside a module-scoped fixture, never while the
module is imported: only the worker that runs this file loads the TPU
library.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.engine import EngineConfig, UniformEngine

# (op, batch + input spatial + cin, kernel + cin + cout, stride, padding)
LAYERS = {
    # networks.dcgan() deconv2: 8x8x512 -> 16x16x256
    "dcgan_deconv2": ("deconv", (2, 8, 8, 512), (3, 3, 512, 256), 2,
                      ((0, 1), (0, 1))),
    # networks.vnet_graph() enc4: 32x32x16x64 -> 16x16x8x128
    "vnet_enc4": ("conv", (1, 32, 32, 16, 64), (3, 3, 3, 64, 128), 2,
                  ((1, 1),) * 3),
    # networks.vnet_graph() up2: 16x16x8x128 -> 32x32x16x64
    "vnet_up2": ("deconv", (1, 16, 16, 8, 128), (3, 3, 3, 128, 64), 2,
                 ((0, 1),) * 3),
    # networks.vnet_graph() head: 1x1x1 conv 16 -> 2 classes
    "vnet_head": ("conv", (1, 32, 32, 16, 16), (1, 1, 1, 16, 2), 1, 0),
    # networks.vnet_graph() enc1 and merge4 at the published 128x128x64:
    # 16-lane outputs, so the conv body folds the in-plane taps
    "vnet_enc1": ("conv", (1, 128, 128, 64, 1), (3, 3, 3, 1, 16), 1, 1),
    "vnet_merge4": ("conv", (1, 128, 128, 64, 32), (3, 3, 3, 32, 16), 1, 1),
}
# Served at full resolution, not trained there: one leading-dim row of the
# dw kernel's working set is over the scoped-VMEM cap at 128x128x64.
FORWARD_ONLY = {"vnet_enc1", "vnet_merge4"}
CASES = [pytest.param(layer, dtype, mode, id=f"{layer}-{dtype}-{mode}")
         for layer in sorted(LAYERS)
         for dtype in ("float32", "bfloat16")
         for mode in ("fwd", "grad")
         if mode == "fwd" or layer not in FORWARD_ONLY]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def engine():
    return UniformEngine(EngineConfig(method="pallas", interpret=False))


def _compile(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("layer,dtype,mode", CASES)
def test_engine_op_compiles_for_v5e(one_chip, engine, layer, dtype, mode):
    op, xs, ws, stride, padding = LAYERS[layer]
    dt = jnp.dtype(dtype)
    args = (jax.ShapeDtypeStruct(xs, dt, sharding=one_chip),
            jax.ShapeDtypeStruct(ws, dt, sharding=one_chip))

    def fwd(x, w):
        return getattr(engine, op)(x, w, stride, padding)

    if mode == "fwd":
        text = _compile(fwd, args)
        assert text.count("tpu_custom_call") >= 1
    else:
        text = _compile(jax.grad(
            lambda x, w: fwd(x, w).astype(jnp.float32).sum(), (0, 1)), args)
        # the dx and dw kernels, both lowered by Mosaic
        assert text.count('custom_call_target="tpu_custom_call"') == 2, \
            text.count("tpu_custom_call")


def test_int8_weight_deconv_compiles_for_v5e(one_chip, engine):
    """int8 weights with the per-channel dequant fused in the epilogue."""
    _, xs, ws, stride, padding = LAYERS["dcgan_deconv2"]
    args = (jax.ShapeDtypeStruct(xs, jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct(ws, jnp.int8, sharding=one_chip),
            jax.ShapeDtypeStruct((ws[-1],), jnp.float32, sharding=one_chip))
    text = _compile(lambda x, w, s: engine.deconv(x, w, stride, padding,
                                                  w_scale=s), args)
    assert "tpu_custom_call" in text


def test_merge4_plan_covers_kernel_vmem(one_chip, monkeypatch):
    """V-Net merge4 at 128x128x64 (bf16, the served cell's budget): the
    planned step fits the 48 MiB budget under ``strict_vmem``, and Mosaic
    lowers the folded kernel with its scoped VMEM capped at exactly the
    planned ``step_vmem_bytes`` — the byte model covers what the kernel
    allocates."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.conv import kernel as conv_kernel
    from repro.kernels.common import fold_width

    budget = 48 << 20
    eng = UniformEngine(EngineConfig(method="pallas", interpret=False,
                                     strict_vmem=True, max_tile_bytes=budget))
    _, xs, ws, stride, padding = LAYERS["vnet_merge4"]
    plan = eng.plan("conv", (130, 130, 66), (3, 3, 3), (1, 1, 1), 32, 16)
    assert plan.dtile == 1 and plan.block_co == 16
    assert fold_width((3, 3, 3), (1, 1, 1), (1, 1, 1), plan.block_co) == 9
    assert plan.step_vmem_bytes <= budget
    monkeypatch.setattr(
        conv_kernel, "compiler_params",
        lambda step, sem: pltpu.CompilerParams(
            dimension_semantics=tuple(sem),
            vmem_limit_bytes=plan.step_vmem_bytes))
    args = (jax.ShapeDtypeStruct(xs, jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct(ws, jnp.bfloat16, sharding=one_chip))
    text = _compile(lambda x, w: eng.conv(x, w, stride, padding), args)
    assert text.count("tpu_custom_call") >= 1
