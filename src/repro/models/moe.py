"""Mixture-of-experts block: top-k routing with sort-based capacity dispatch.

Dispatch is the dropping flavour (GShard capacity) implemented without the
O(T*E*C) one-hot tensor: (token, k) pairs are sorted by expert id, ranked
within their expert via a running offset, and scattered into a dense
[E, C, D] buffer that is sharded over the ``model`` axis (expert
parallelism).  Everything is differentiable (gradients flow through the
gathers/scatters and the router probabilities).

arctic-480b adds a dense residual MLP in parallel (``cfg.residual_mlp``).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.sharding import compat
from repro.sharding.partition import constrain


class MoeParams(NamedTuple):
    w_router: jax.Array       # [D, E]
    w_in: jax.Array           # [E, D, F]
    w_gate: jax.Array | None  # [E, D, F]
    w_out: jax.Array          # [E, F, D]


def init_moe(key, cfg: ModelConfig) -> MoeParams:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return MoeParams(
        w_router=L.dense_init(ks[0], (d, e), (None, None), scale=0.02),
        w_in=L.dense_init(ks[1], (e, d, f), ("model", "fsdp", None)),
        w_gate=(L.dense_init(ks[2], (e, d, f), ("model", "fsdp", None))
                if cfg.gated_mlp else None),
        w_out=L.dense_init(ks[3], (e, f, d), ("model", None, "fsdp")),
    )


def capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = math.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    c = min(max(-(-c // 128) * 128, 128), n_tokens * cfg.top_k)
    return c


def moe(p: MoeParams, x: jax.Array, cfg: ModelConfig):
    """x [B, S, D] -> (y [B, S, D], aux_loss scalar)."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p.w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                     # [T, E]
    top_p, top_e = jax.lax.top_k(probs, k)                      # [T, k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    flat_e = top_e.reshape(-1)                                  # [T*k]
    flat_p = top_p.reshape(-1)
    c = capacity(t, cfg)

    sort_idx = jnp.argsort(flat_e)                              # stable
    sorted_e = flat_e[sort_idx]
    offs = jnp.searchsorted(sorted_e, jnp.arange(e))            # [E]
    rank = jnp.arange(t * k) - offs[sorted_e]
    keep = rank < c
    dest = jnp.where(keep, sorted_e * c + rank, e * c)          # overflow slot
    tok = sort_idx // k

    xs = jnp.take(xf, tok, axis=0)                              # [T*k, D]
    buf = jnp.zeros((e * c + 1, d), x.dtype).at[dest].set(xs)
    buf = buf[:e * c].reshape(e, c, d)
    buf = constrain(buf, "model", "batch", None)

    act = L.activation(cfg.mlp_activation)
    h = jnp.einsum("ecd,edf->ecf", buf, p.w_in.astype(x.dtype))
    h = constrain(h, "model", "batch", None)
    if p.w_gate is not None:
        g = jnp.einsum("ecd,edf->ecf", buf, p.w_gate.astype(x.dtype))
        h = act(g) * h
    else:
        h = act(h)
    y_e = jnp.einsum("ecf,efd->ecd", h, p.w_out.astype(x.dtype))
    y_e = constrain(y_e, "model", "batch", None)

    y_flat = jnp.concatenate(
        [y_e.reshape(e * c, d), jnp.zeros((1, d), y_e.dtype)], axis=0)
    ys = jnp.take(y_flat, dest, axis=0)                         # [T*k, D]
    # bf16 combine (weights in bf16; top_k<=8 summands — §Perf: halves the
    # [T*k, D] transient vs the f32 version)
    weighted = ys * flat_p[sort_idx][:, None].astype(ys.dtype)
    out = jax.ops.segment_sum(weighted, tok, num_segments=t)    # [T, D]
    out = out.astype(x.dtype).reshape(b, s, d)
    out = constrain(out, "batch", None, None)
    out = checkpoint_name(out, "blk_out")

    # load-balance auxiliary loss (Switch/GShard form)
    frac = jnp.bincount(flat_e, length=e).astype(jnp.float32) / (t * k)
    aux = e * jnp.sum(frac * probs.mean(axis=0))
    return out, aux


# ---------------------------------------------------------------------------
# §Perf variant: explicit expert parallelism via shard_map.
#
# Baseline ("dense_scatter") scatters data-sharded tokens into a
# model-sharded [E, C, D] buffer and lets XLA SPMD invent the collectives —
# the HLO shows it all-gathers the token buffer onto every model shard.
# This variant instead computes the (cheap) routing redundantly on every
# model shard, keeps ONLY the local experts' buffer, and combines with a
# single psum over the model axis — collective cost = one [T_loc, D]
# all-reduce per layer, independent of E.
# ---------------------------------------------------------------------------

def moe_shardmap(p: MoeParams, x: jax.Array, cfg: ModelConfig):
    from jax.sharding import PartitionSpec as P
    from repro.sharding.partition import get_abstract_mesh_or_none

    mesh = get_abstract_mesh_or_none()
    if mesh is None or "model" not in mesh.axis_names:
        return moe(p, x, cfg)
    m_size = mesh.shape["model"]
    e_total, k = cfg.n_experts, cfg.top_k
    if e_total % m_size != 0:
        return moe(p, x, cfg)
    e_loc = e_total // m_size
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = P(batch_axes if batch_axes else None, None, None)
    wspec = P("model", None, None)
    d = x.shape[-1]
    act = L.activation(cfg.mlp_activation)

    def one_group(xf, wr, wi, wg, wo):
        """Dispatch+compute one token group xf [Tg, D] locally."""
        t = xf.shape[0]
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            wr.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        flat_e = top_e.reshape(-1)
        c = capacity(t, cfg)
        sort_idx = jnp.argsort(flat_e)
        sorted_e = flat_e[sort_idx]
        offs = jnp.searchsorted(sorted_e, jnp.arange(e_total))
        rank = jnp.arange(t * k) - offs[sorted_e]
        e0 = jax.lax.axis_index("model") * e_loc
        in_range = (sorted_e >= e0) & (sorted_e < e0 + e_loc)
        keep = (rank < c) & in_range
        dest = jnp.where(keep, (sorted_e - e0) * c + rank, e_loc * c)
        tok = sort_idx // k

        xs = jnp.take(xf, tok, axis=0)
        buf = jnp.zeros((e_loc * c + 1, d), x.dtype).at[dest].set(xs)
        buf = buf[:e_loc * c].reshape(e_loc, c, d)
        h = jnp.einsum("ecd,edf->ecf", buf, wi)
        if wg is not None:
            h = act(jnp.einsum("ecd,edf->ecf", buf, wg)) * h
        else:
            h = act(h)
        y_e = jnp.einsum("ecf,efd->ecd", h, wo)
        y_flat = jnp.concatenate(
            [y_e.reshape(e_loc * c, d), jnp.zeros((1, d), y_e.dtype)], 0)
        ys = jnp.take(y_flat, dest, axis=0)
        weighted = ys * top_p.reshape(-1)[sort_idx][:, None].astype(ys.dtype)
        out = jax.ops.segment_sum(weighted, tok, num_segments=t)
        out = jax.lax.psum(out.astype(x.dtype), "model")   # THE collective

        frac = jnp.bincount(flat_e, length=e_total).astype(jnp.float32) \
            / (t * k)
        aux = e_total * jnp.sum(frac * probs.mean(axis=0))
        return out, aux

    def local(xl, wr, wi, wg, wo):
        b_loc, s, _ = xl.shape
        t = b_loc * s
        xf = xl.reshape(t, d)
        g = cfg.moe_groups if t % max(cfg.moe_groups, 1) == 0 else 1
        if g <= 1:
            out, aux = one_group(xf, wr, wi, wg, wo)
        else:
            # token groups: dispatch transients shrink by g; the scan body
            # is checkpointed so backward re-derives one group at a time
            from repro.models.flags import maybe_scan

            def body(_, xg):
                o, a = one_group(xg, wr, wi, wg, wo)
                return 0, (o, a)

            _, (outs, auxs) = maybe_scan(jax.checkpoint(body), 0,
                                         xf.reshape(g, t // g, d))
            out, aux = outs.reshape(t, d), jnp.mean(auxs)
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return out.reshape(b_loc, s, d), aux

    wi = p.w_in.astype(x.dtype)
    wo = p.w_out.astype(x.dtype)
    if p.w_gate is not None:
        wg = p.w_gate.astype(x.dtype)
        body, args = local, (x, p.w_router, wi, wg, wo)
        specs_in = (dp, P(), wspec, wspec, wspec)
    else:
        body = lambda xl, wr, wi_, wo_: local(xl, wr, wi_, None, wo_)
        args = (x, p.w_router, wi, wo)
        specs_in = (dp, P(), wspec, wspec)
    fn = compat.shard_map_norep(body, mesh=mesh, in_specs=specs_in,
                                out_specs=(dp, P()))
    out, aux = fn(*args)
    out = checkpoint_name(out, "blk_out")
    return out, aux


def moe_dispatch(p: MoeParams, x: jax.Array, cfg: ModelConfig):
    """Entry point honouring cfg.moe_impl."""
    if cfg.moe_impl == "shardmap":
        return moe_shardmap(p, x, cfg)
    return moe(p, x, cfg)
