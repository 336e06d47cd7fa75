"""DCGAN (Radford et al., arXiv:1511.06434): the generator's deconv stack
after the z-projection, as served: sizes, plain reference, and the program
entry that serves it.

The reference below is the deconv stack in plain f32 ``lax``/``jnp``
(``bench/reference.py``); it imports nothing of the program.
``program_served_model`` is the only place this configuration touches the
system under test.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import flops as F
import reference as R


# -- shapes -------------------------------------------------------------------

def gen_layers(cfg) -> list[F.Layer]:
    ch, sp, k, s = cfg["generator_channels"], cfg["generator_start"], \
        cfg["kernel"], cfg["stride"]
    out = []
    for i in range(len(ch) - 1):
        out.append(F.Layer(f"gen.deconv{i + 1}", "deconv", (sp, sp), ch[i],
                           ch[i + 1], (k, k), (s, s),
                           (tuple(cfg["deconv_crop"]),) * 2))
        sp *= s
    return out


def request_shape(cfg) -> tuple[int, ...]:
    first = gen_layers(cfg)[0]
    return (*first.in_spatial, first.cin)


def serve_passes(cfg, batch: int) -> list[tuple[F.Layer, str, int]]:
    return [(l, "fwd", batch) for l in gen_layers(cfg)]


# -- weights (the benchmark's own, from the seed, on the device) --------------

def _he(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * math.sqrt(2.0 / fan_in)


def _deconv_fan_in(l: F.Layer) -> float:
    return l.cin * math.prod(k / s for k, s in zip(l.kernel, l.stride))


def init_served(cfg, key, dtype, names):
    """Served generator weights keyed by the program graph's layer names."""
    g = gen_layers(cfg)
    ks = jax.random.split(key, 2 * len(g))
    return {n: {"w": _he(ks[2 * i], (*l.kernel, l.cin, l.cout),
                         _deconv_fan_in(l)).astype(dtype),
                "b": (0.1 * jax.random.normal(ks[2 * i + 1], (l.cout,))
                      ).astype(dtype)}
            for i, (n, l) in enumerate(zip(names, g))}


# -- the plain reference ------------------------------------------------------

def _acts(cfg, n):
    hid = cfg["generator_activations"]["hidden"]
    return [hid] * (n - 1) + [cfg["generator_activations"]["output"]]


def ref_deconv_stack(cfg, ws, h):
    """``ws``: list of ``{"w", "b"}`` per generator layer, f32."""
    layers = gen_layers(cfg)
    for l, w, act in zip(layers, ws, _acts(cfg, len(layers))):
        h = R.deconv(h, w["w"], l.stride, l.padding) + w["b"]
        h = R.activation(h, act)
    return h


# -- the program entries ------------------------------------------------------

def program_served_model(cfg, key, dtype):
    """``DcnnServer`` model spec of the generator's deconv stack, with this
    benchmark's weights in place of the program's."""
    from repro.runtime.dcnn_server import dcgan_gen_spec
    spec = dcgan_gen_spec(jax.random.PRNGKey(0),
                          start=cfg["generator_start"],
                          chans=tuple(cfg["generator_channels"]),
                          name="dcgan_gen")
    glayers = spec.graph_for(None).layers
    got = [(l.cin, l.cout, l.kernel, l.stride, l.padding, l.in_spatial)
           for l in glayers]
    want = [(l.cin, l.cout, l.kernel, l.stride, l.padding, l.in_spatial)
            for l in gen_layers(cfg)]
    if got != want:
        raise RuntimeError(f"the program's DCGAN layers {got} are not this "
                           f"configuration's {want}")
    names = [l.name for l in glayers]
    spec.weights = jax.jit(lambda k: init_served(cfg, k, dtype, names))(key)
    return spec


def reference_served(cfg, weights, names, x):
    ws = [{k: v.astype(jnp.float32) for k, v in weights[n].items()}
          for n in names]
    return ref_deconv_stack(cfg, ws, x.astype(jnp.float32))
