"""V-Net (Milletari et al., arXiv:1606.04797) as the program builds it:
sizes, plain reference, and the program entries that serve it.

Encoder: one 3x3x3 conv per stage (stride 1 at the first stage, stride 2
below), ReLU.  Decoder: a 3x3x3 stride-2 deconv up (crop (0, 1)), ReLU,
concatenated with the encoder's skip, then a 3x3x3 merge conv back to the
skip's channels, ReLU.  Head: a 1x1x1 conv to the classes, no activation.
No layer has a bias.  The reference imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import flops as F
import reference as R


def layers(cfg) -> dict[str, F.Layer]:
    """Every conv/deconv in graph order, keyed by the program's names."""
    k, sp, ci = cfg["kernel"], tuple(cfg["in_spatial"]), cfg["in_channels"]
    rank = len(sp)
    pad1, crop = ((1, 1),) * rank, (tuple(cfg["deconv_crop"]),) * rank
    out, skips = {}, []
    for i, co in enumerate(cfg["stage_channels"]):
        st = (1 if i == 0 else 2,) * rank
        lay = F.Layer(f"vnet.enc{i + 1}", "conv", sp, ci, co, (k,) * rank, st,
                      pad1)
        out[lay.name] = lay
        sp, ci = lay.out_spatial, co
        skips.append((co, sp))
    for i, (sc, ssp) in enumerate(reversed(skips[:-1])):
        up = F.Layer(f"vnet.up{i + 1}", "deconv", sp, ci, sc, (k,) * rank,
                     (2,) * rank, crop)
        merge = F.Layer(f"vnet.merge{i + 1}", "conv", ssp, 2 * sc, sc,
                        (k,) * rank, (1,) * rank, pad1)
        out[up.name], out[merge.name] = up, merge
        sp, ci = ssp, sc
    out["vnet.head"] = F.Layer("vnet.head", "conv", sp, ci,
                               cfg["num_classes"], (1,) * rank, (1,) * rank,
                               ((0, 0),) * rank)
    return out


def request_shape(cfg) -> tuple[int, ...]:
    return (*cfg["in_spatial"], cfg["in_channels"])


def serve_passes(cfg, batch: int):
    return [(l, "fwd", batch) for l in layers(cfg).values()]


def init_served(cfg, key, dtype):
    ls = layers(cfg)
    ks = jax.random.split(key, len(ls))
    return {n: (jax.random.normal(k, (*l.kernel, l.cin, l.cout), jnp.float32)
                * math.sqrt(2.0 / (l.cin * math.prod(l.kernel)))).astype(dtype)
            for k, (n, l) in zip(ks, ls.items())}


def reference_served(cfg, weights, names, x):
    """Logits of ``x`` [B, *spatial, cin] in f32 at HIGHEST precision."""
    del names
    ls = layers(cfg)
    w = {n: v.astype(jnp.float32) for n, v in weights.items()}
    h = x.astype(jnp.float32)
    n_stages = len(cfg["stage_channels"])
    skips = []
    for i in range(n_stages):
        l = ls[f"vnet.enc{i + 1}"]
        h = R.activation(R.conv(h, w[l.name], l.stride, l.padding),
                         cfg["activation"])
        skips.append(h)
    for i, skip in enumerate(reversed(skips[:-1])):
        up, merge = ls[f"vnet.up{i + 1}"], ls[f"vnet.merge{i + 1}"]
        h = R.activation(R.deconv(h, w[up.name], up.stride, up.padding),
                         cfg["activation"])
        h = jnp.concatenate([h, skip], axis=-1)
        h = R.activation(R.conv(h, w[merge.name], merge.stride,
                                merge.padding), cfg["activation"])
    head = ls["vnet.head"]
    return R.conv(h, w[head.name], head.stride, head.padding)


def program_served_model(cfg, key, dtype):
    """``DcnnServer`` model spec of V-Net with this benchmark's weights."""
    from repro.runtime.dcnn_server import vnet_spec
    spec = vnet_spec(jax.random.PRNGKey(0),
                     chans=tuple(cfg["stage_channels"]),
                     cin=cfg["in_channels"], num_classes=cfg["num_classes"],
                     base_spatial=tuple(cfg["in_spatial"]), name="vnet")
    names = [l.name for l in spec.graph_for(None).layers]
    if sorted(names) != sorted(layers(cfg)):
        raise RuntimeError(f"the program's V-Net layers {names} are not this "
                           f"configuration's {list(layers(cfg))}")
    spec.weights = jax.jit(lambda k: init_served(cfg, k, dtype))(key)
    return spec
