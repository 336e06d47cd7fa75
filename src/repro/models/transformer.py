"""LM assembly for all assigned families.

* dense / vlm:     pre-norm GQA attention + MLP, scan-over-layers + remat
* moe:             attention + top-k MoE (+ optional dense residual MLP)
* ssm (xlstm):     python-stacked mLSTM/sLSTM blocks (heterogeneous layers)
* hybrid (zamba2): grouped scan — 6 Mamba2 layers per group, one *shared*
                   attention+MLP block applied between groups (its KV cache
                   has one slot per application, not per layer)
* encdec (whisper):encoder stack (stub frame embeddings) + causal decoder
                   with per-layer cross attention

Modes: train (loss), prefill (last-position logits + cache), decode
(one token + cache).  All activations carry logical sharding constraints.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as S
from repro.models.flags import maybe_scan
from repro.models.mlp import MlpParams, init_mlp, mlp
from repro.sharding import compat
from repro.sharding.partition import WS, constrain


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------

def _stack_layers(key, n: int, init_fn):
    """vmap an init over layer keys -> stacked [L, ...] params; logical axes
    gain a leading None (the scan dim)."""
    keys = jax.random.split(key, n)
    stacked = jax.vmap(init_fn)(keys)
    return jax.tree_util.tree_map(
        lambda ws: WS(ws.value, (None,) + tuple(ws.logical)),
        stacked, is_leaf=lambda x: isinstance(x, WS))


def _init_dense_layer(cfg: ModelConfig):
    def init(key):
        k1, k2 = jax.random.split(key)
        return {"norm1": L.ones_init((cfg.d_model,), (None,)),
                "attn": A.init_attention(k1, cfg),
                "norm2": L.ones_init((cfg.d_model,), (None,)),
                "mlp": init_mlp(k2, cfg)}
    return init


def _init_moe_layer(cfg: ModelConfig):
    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        p = {"norm1": L.ones_init((cfg.d_model,), (None,)),
             "attn": A.init_attention(k1, cfg),
             "norm2": L.ones_init((cfg.d_model,), (None,)),
             "moe": MOE.init_moe(k2, cfg)}
        if cfg.residual_mlp:
            p["res_mlp"] = init_mlp(k3, cfg)
        return p
    return init


def _init_encdec_layers(cfg: ModelConfig, key):
    ke, kd = jax.random.split(key)

    def enc_init(k):
        k1, k2 = jax.random.split(k)
        return {"norm1": L.ones_init((cfg.d_model,), (None,)),
                "attn": A.init_attention(k1, cfg),
                "norm2": L.ones_init((cfg.d_model,), (None,)),
                "mlp": init_mlp(k2, cfg)}

    def dec_init(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"norm1": L.ones_init((cfg.d_model,), (None,)),
                "self_attn": A.init_attention(k1, cfg),
                "norm_x": L.ones_init((cfg.d_model,), (None,)),
                "cross_attn": A.init_attention(k2, cfg),
                "norm2": L.ones_init((cfg.d_model,), (None,)),
                "mlp": init_mlp(k3, cfg)}

    return (_stack_layers(ke, cfg.n_enc_layers, enc_init),
            _stack_layers(kd, cfg.n_layers, dec_init))


def init_params(cfg: ModelConfig, key) -> dict:
    keys = jax.random.split(key, 8)
    d = cfg.d_model
    params: dict[str, Any] = {
        "embed": L.dense_init(keys[0], (cfg.vocab, d), ("model", "fsdp"),
                              scale=0.02),
        "final_norm": L.ones_init((d,), (None,)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[1], (cfg.vocab, d),
                                         ("model", "fsdp"), scale=0.02)

    fam = cfg.family
    if fam in ("dense", "vlm"):
        params["layers"] = _stack_layers(keys[2], cfg.n_layers,
                                         _init_dense_layer(cfg))
    elif fam == "moe":
        params["layers"] = _stack_layers(keys[2], cfg.n_layers,
                                         _init_moe_layer(cfg))
    elif fam == "ssm":
        assert cfg.ssm_block == "xlstm"
        layer_list = []
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        for i in range(cfg.n_layers):
            if cfg.slstm_every and i % cfg.slstm_every == 0:
                layer_list.append(S.init_slstm(lkeys[i], cfg))
            else:
                layer_list.append(S.init_mlstm(lkeys[i], cfg))
        params["layers"] = layer_list
    elif fam == "hybrid":
        assert cfg.ssm_block == "mamba2" and cfg.attn_every
        assert cfg.n_layers % cfg.attn_every == 0
        params["layers"] = _stack_layers(
            keys[2], cfg.n_layers, lambda k: S.init_mamba2(k, cfg))
        k1, k2 = jax.random.split(keys[3])
        params["shared_attn"] = {
            "norm1": L.ones_init((d,), (None,)),
            "attn": A.init_attention(k1, cfg),
            "norm2": L.ones_init((d,), (None,)),
            "mlp": init_mlp(k2, cfg)}
    elif fam == "encdec":
        enc, dec = _init_encdec_layers(cfg, keys[2])
        params["encoder_layers"] = enc
        params["layers"] = dec
        params["enc_pos"] = L.dense_init(keys[4], (cfg.enc_seq, d),
                                         (None, None), scale=0.02)
        params["enc_final_norm"] = L.ones_init((d,), (None,))
    else:
        raise ValueError(fam)
    return params


def param_count(values) -> int:
    return sum(v.size for v in jax.tree_util.tree_leaves(values))


def active_param_count(values, cfg: ModelConfig) -> int:
    """Parameters touched per token (MoE: top_k of n_experts)."""
    total = param_count(values)
    if cfg.family != "moe":
        return total
    expert = sum(
        v.size for p in ["w_in", "w_gate", "w_out"]
        for v in jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(lambda x: x,
                                   _extract_moe_leaves(values, p)))
    )
    return total - expert + int(expert * cfg.top_k / cfg.n_experts)


def _extract_moe_leaves(values, field):
    out = []
    def visit(node):
        if isinstance(node, MOE.MoeParams):
            v = getattr(node, field)
            if v is not None:
                out.append(v)
        elif isinstance(node, dict):
            for x in node.values():
                visit(x)
        elif isinstance(node, (list, tuple)):
            for x in node:
                visit(x)
    visit(values)
    return out


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _dense_block(lp, h, cfg, cos, sin, kv=None, pos=None):
    a, new_kv = A.attention(
        lp["attn"], L.rmsnorm(h, lp["norm1"], cfg.norm_eps), cfg,
        cos=cos, sin=sin, kv_cache=kv, cache_pos=pos)
    h = h + a
    m = mlp(lp["mlp"], L.rmsnorm(h, lp["norm2"], cfg.norm_eps), cfg)
    return h + m, new_kv


def _moe_block(lp, h, cfg, cos, sin, kv=None, pos=None):
    a, new_kv = A.attention(
        lp["attn"], L.rmsnorm(h, lp["norm1"], cfg.norm_eps), cfg,
        cos=cos, sin=sin, kv_cache=kv, cache_pos=pos)
    h = h + a
    hn = L.rmsnorm(h, lp["norm2"], cfg.norm_eps)
    m, aux = MOE.moe_dispatch(lp["moe"], hn, cfg)
    if "res_mlp" in lp:
        m = m + mlp(lp["res_mlp"], hn, cfg)
    return h + m, new_kv, aux


def _remat(fn, cfg):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "save_outs":
        # save the post-collective block outputs: backward never re-runs
        # the out-projection psums (collective term) nor their matmuls
        policy = jax.checkpoint_policies.save_only_these_names("blk_out")
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------------------
# Positions
# ---------------------------------------------------------------------------

def _rope(cfg: ModelConfig, positions, mrope_positions=None):
    hd = cfg.resolved_head_dim
    if cfg.mrope:
        if mrope_positions is None:
            mrope_positions = jnp.broadcast_to(positions[None], (3, *positions.shape))
        return L.mrope_cos_sin(mrope_positions, hd, cfg.mrope_sections,
                               cfg.rope_theta)
    return L.rope_cos_sin(positions, hd, cfg.rope_theta)


# ---------------------------------------------------------------------------
# Backbone
# ---------------------------------------------------------------------------

def backbone(params, cfg: ModelConfig, h, *, mode: str, cache=None,
             positions, mrope_positions=None, enc_out=None):
    """h [B,S,D] -> (h, new_cache, aux_loss)."""
    cos, sin = _rope(cfg, positions, mrope_positions)
    aux = jnp.zeros((), jnp.float32)
    fam = cfg.family
    decode = mode == "decode"
    pos = cache["pos"] if cache is not None and "pos" in cache else None
    new_cache = {}

    if fam in ("dense", "vlm", "moe"):
        is_moe = fam == "moe"

        if decode:
            def body(carry, xs):
                hh, aa = carry
                lp, (kc, vc) = xs
                if is_moe:
                    hh, kv, a = _moe_block(lp, hh, cfg, cos, sin, (kc, vc), pos)
                    aa = aa + a
                else:
                    hh, kv = _dense_block(lp, hh, cfg, cos, sin, (kc, vc), pos)
                return (hh, aa), kv

            (h, aux), kvs = maybe_scan(
                _remat(body, cfg), (h, aux), (params["layers"], cache["kv"]))
            new_cache = {"kv": kvs, "pos": pos + 1}
        else:
            def body(carry, lp):
                hh, aa = carry
                if is_moe:
                    hh, kv, a = _moe_block(lp, hh, cfg, cos, sin)
                    aa = aa + a
                else:
                    hh, kv = _dense_block(lp, hh, cfg, cos, sin)
                return (hh, aa), kv if mode == "prefill" else 0

            seg = cfg.remat_segments
            if (mode == "train" and seg and cfg.n_layers % seg == 0
                    and seg < cfg.n_layers):
                # nested remat: the residual stream is saved once per
                # SEGMENT (L/seg saves instead of L); backward re-runs a
                # segment's forward, inside which per-layer remat applies.
                g = cfg.n_layers // seg
                lp_seg = jax.tree_util.tree_map(
                    lambda v: v.reshape(seg, g, *v.shape[1:]),
                    params["layers"])

                def seg_body(carry, lp_g):
                    c2, _ = maybe_scan(_remat(body, cfg), carry, lp_g)
                    return c2, 0

                (h, aux), _ = maybe_scan(
                    jax.checkpoint(
                        seg_body,
                        policy=jax.checkpoint_policies.nothing_saveable),
                    (h, aux), lp_seg)
                kvs = 0
            else:
                (h, aux), kvs = maybe_scan(
                    _remat(body, cfg), (h, aux), params["layers"])
            if mode == "prefill":
                new_cache = {"kv": kvs, "pos": jnp.asarray(h.shape[1], jnp.int32)}

    elif fam == "ssm":
        states = cache["states"] if cache else [None] * cfg.n_layers
        new_states = []
        for i, lp in enumerate(params["layers"]):
            slstm = cfg.slstm_every and i % cfg.slstm_every == 0
            if decode:
                if slstm:
                    h, st = S.slstm_decode(lp, h, cfg, states[i])
                else:
                    h, st = S.mlstm_decode(lp, h, cfg, states[i])
            else:
                if slstm:
                    h, st = S.slstm_block(lp, h, cfg, states[i])
                else:
                    h, st = S.mlstm_block(lp, h, cfg, states[i])
            new_states.append(st)
        if mode != "train":
            new_cache = {"states": new_states,
                         "pos": (pos + 1) if decode else
                         jnp.asarray(h.shape[1], jnp.int32)}

    elif fam == "hybrid":
        groups = cfg.n_layers // cfg.attn_every
        lp_grouped = jax.tree_util.tree_map(
            lambda x: x.reshape(groups, cfg.attn_every, *x.shape[1:]),
            params["layers"])
        ssm_states = cache["ssm"] if cache else None
        kv_cache = cache["kv"] if cache else None
        new_ssm, new_kv = [], []
        sp = params["shared_attn"]
        for g in range(groups):
            lp_g = jax.tree_util.tree_map(lambda x: x[g], lp_grouped)
            st_g = (jax.tree_util.tree_map(lambda x: x[g], ssm_states)
                    if ssm_states is not None else None)

            if decode:
                def body(hh, xs):
                    lp, st = xs
                    hh, st2 = S.mamba2_decode(lp, hh, cfg, st)
                    return hh, st2
                h, st_out = maybe_scan(_remat(body, cfg), h, (lp_g, st_g))
            else:
                def body(hh, lp):
                    hh, st2 = S.mamba2_block(lp, hh, cfg)
                    return hh, st2
                h, st_out = maybe_scan(_remat(body, cfg), h, lp_g)
            new_ssm.append(st_out)
            # shared attention block between groups
            kv_g = (jax.tree_util.tree_map(lambda x: x[g], kv_cache)
                    if kv_cache is not None else None)
            a, kv_out = A.attention(
                sp["attn"], L.rmsnorm(h, sp["norm1"], cfg.norm_eps), cfg,
                cos=cos, sin=sin, kv_cache=kv_g, cache_pos=pos)
            h = h + a
            h = h + mlp(sp["mlp"], L.rmsnorm(h, sp["norm2"], cfg.norm_eps), cfg)
            new_kv.append(kv_out)
        if mode != "train":
            stack = lambda xs: jax.tree_util.tree_map(
                lambda *y: jnp.stack(y), *xs)
            new_cache = {"ssm": stack(new_ssm), "kv": stack(new_kv),
                         "pos": (pos + 1) if decode else
                         jnp.asarray(h.shape[1], jnp.int32)}

    elif fam == "encdec":
        assert enc_out is not None
        cross = cache.get("cross") if cache else None
        if decode:
            def body(hh, xs):
                lp, (kc, vc), (xk, xv) = xs
                a, kv = A.attention(
                    lp["self_attn"], L.rmsnorm(hh, lp["norm1"], cfg.norm_eps),
                    cfg, cos=cos, sin=sin, kv_cache=(kc, vc), cache_pos=pos)
                hh = hh + a
                c, _ = A.attention(
                    lp["cross_attn"], L.rmsnorm(hh, lp["norm_x"], cfg.norm_eps),
                    cfg, xattn_kv=(xk, xv))
                hh = hh + c
                hh = hh + mlp(lp["mlp"], L.rmsnorm(hh, lp["norm2"],
                                                   cfg.norm_eps), cfg)
                return hh, kv
            h, kvs = maybe_scan(_remat(body, cfg), h,
                              (params["layers"], cache["kv"], cross))
            new_cache = {"kv": kvs, "cross": cross, "pos": pos + 1}
        else:
            def body(hh, lp):
                a, kv = A.attention(
                    lp["self_attn"], L.rmsnorm(hh, lp["norm1"], cfg.norm_eps),
                    cfg, cos=cos, sin=sin)
                hh = hh + a
                xk = jnp.einsum("btd,dhk->bthk", enc_out,
                                lp["cross_attn"].wk.astype(hh.dtype))
                xv = jnp.einsum("btd,dhk->bthk", enc_out,
                                lp["cross_attn"].wv.astype(hh.dtype))
                c, _ = A.attention(
                    lp["cross_attn"], L.rmsnorm(hh, lp["norm_x"], cfg.norm_eps),
                    cfg, xattn_kv=(xk, xv))
                hh = hh + c
                hh = hh + mlp(lp["mlp"], L.rmsnorm(hh, lp["norm2"],
                                                   cfg.norm_eps), cfg)
                return hh, (kv, (xk, xv)) if mode == "prefill" else 0
            h, out = maybe_scan(_remat(body, cfg), h, params["layers"])
            if mode == "prefill":
                kvs, cross = out
                new_cache = {"kv": kvs, "cross": cross,
                             "pos": jnp.asarray(h.shape[1], jnp.int32)}
    else:
        raise ValueError(fam)

    return h, new_cache, aux


def encode(params, cfg: ModelConfig, enc_embeds):
    """Whisper encoder over stub frame embeddings [B, T, D]."""
    h = enc_embeds + params["enc_pos"].astype(enc_embeds.dtype)[None]
    h = constrain(h, "batch", None, None)

    def body(hh, lp):
        a, _ = A.attention(lp["attn"],
                           L.rmsnorm(hh, lp["norm1"], cfg.norm_eps), cfg,
                           causal=False)
        hh = hh + a
        hh = hh + mlp(lp["mlp"], L.rmsnorm(hh, lp["norm2"], cfg.norm_eps), cfg)
        return hh, 0

    h, _ = maybe_scan(_remat(body, cfg), h, params["encoder_layers"])
    return L.rmsnorm(h, params["enc_final_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Heads / losses / entry points
# ---------------------------------------------------------------------------

def logits_fn(params, cfg: ModelConfig, h):
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    table = params.get("lm_head", params["embed"])
    logits = jnp.einsum("bsd,vd->bsv", h, table.astype(h.dtype))
    return constrain(logits, "batch", None, "model")


def cross_entropy(logits, labels, mask=None):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = lse - ll
    if mask is not None:
        return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(loss)


_XENT_CHUNK = 8192


def chunked_xent(params, cfg: ModelConfig, h, labels):
    """Training CE without materialising the full [T, V] logits.

    With a mesh: **vocab-parallel CE under shard_map** (Megatron-style) —
    tokens stay on their data shard, the table stays vocab-sharded, each
    local chunk computes a distributed logsumexp (pmax + psum of [chunk]
    vectors, ~KBs on the wire) and the embedding gradient psums ONCE at the
    shard_map boundary.  §Perf iteration 2: replaces the naive chunk scan
    whose per-chunk resharding cost 17 GB/dev of collectives (iteration 1
    log in EXPERIMENTS.md).

    Without a mesh (CPU tests): plain checkpointed chunk scan.
    """
    from repro.sharding.partition import get_abstract_mesh_or_none
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    table = params.get("lm_head", params["embed"])
    b, s, d = h.shape
    t = b * s
    hf = h.reshape(t, d)
    lf = labels.reshape(t)
    chunk = cfg.xent_chunk or _XENT_CHUNK

    mesh = get_abstract_mesh_or_none()
    if mesh is not None and "model" in mesh.axis_names \
            and cfg.vocab % mesh.shape["model"] == 0:
        return _xent_vocab_parallel(mesh, cfg, hf, lf, table, chunk)

    if t % chunk != 0 or t <= chunk:
        logits = jnp.einsum("td,vd->tv", hf, table.astype(h.dtype))
        logits = constrain(logits, "batch", "model")
        return cross_entropy(logits, lf)
    n = t // chunk

    def body(acc, xs):
        hc, lc = xs
        logits = jnp.einsum("cd,vd->cv", hc, table.astype(h.dtype))
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return acc + jnp.sum(lse - ll), None

    acc, _ = maybe_scan(
        jax.checkpoint(body),
        jnp.zeros((), jnp.float32),
        (hf.reshape(n, chunk, d), lf.reshape(n, chunk)))
    return acc / t


def _xent_vocab_parallel(mesh, cfg, hf, lf, table, chunk):
    from jax.sharding import PartitionSpec as P
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    v_loc_count = mesh.shape["model"]
    t = hf.shape[0]
    d = hf.shape[-1]

    def local(hl, ll, tbl):
        # hl [T_loc, D]; ll [T_loc]; tbl [V_loc, D]
        t_loc = hl.shape[0]
        v_loc = tbl.shape[0]
        v0 = jax.lax.axis_index("model") * v_loc
        c = chunk if t_loc % chunk == 0 and t_loc > chunk else t_loc
        n = t_loc // c

        def body(acc, xs):
            hc, lc = xs
            logits = jnp.einsum("cd,vd->cv", hc, tbl.astype(hc.dtype))
            logits = logits.astype(jnp.float32)
            # distributed logsumexp over the vocab-sharded axis; the max
            # shift is gradient-free (exact for the lse derivative) — the
            # stop_gradient must sit INSIDE pmax so its tangent is a
            # symbolic zero (pmax has no differentiation rule)
            m = jax.lax.pmax(
                jax.lax.stop_gradient(jnp.max(logits, axis=-1)), "model")
            ssum = jax.lax.psum(
                jnp.sum(jnp.exp(logits - m[:, None]), axis=-1), "model")
            lse = m + jnp.log(ssum)
            # label logit lives on exactly one vocab shard
            mine = (lc >= v0) & (lc < v0 + v_loc)
            idx = jnp.clip(lc - v0, 0, v_loc - 1)
            ll_part = jnp.take_along_axis(logits, idx[:, None], axis=-1)[:, 0]
            ll_full = jax.lax.psum(jnp.where(mine, ll_part, 0.0), "model")
            return acc + jnp.sum(lse - ll_full, keepdims=True), None

        acc, _ = maybe_scan(jax.checkpoint(body),
                            jnp.zeros((1,), jnp.float32),
                            (hl.reshape(n, c, d), ll.reshape(n, c)))
        acc = jax.lax.psum(acc, batch_axes) if batch_axes else acc
        return acc

    dp = P(batch_axes if batch_axes else None, None)
    fn = compat.shard_map_norep(local, mesh=mesh,
                                in_specs=(dp, P(dp[0]), P("model", None)),
                                out_specs=P(None))
    return fn(hf, lf, table.astype(hf.dtype))[0] / t


def forward(params, cfg: ModelConfig, batch: dict, *, mode: str = "train",
            cache=None, param_dtype=jnp.bfloat16):
    """Unified entry point.

    batch keys: tokens [B,S]; labels [B,S] (train); enc_embeds (encdec);
    mrope_positions [3,B,S] (vlm); prefix_embeds (vlm smoke).
    """
    tokens = batch["tokens"]
    tokens = constrain(tokens, "batch", None)
    b, s = tokens.shape
    h = L.embed_lookup(params["embed"].astype(param_dtype), tokens)

    if batch.get("prefix_embeds") is not None:
        pe = batch["prefix_embeds"].astype(h.dtype)
        h = jnp.concatenate([pe, h[:, pe.shape[1]:]], axis=1)

    if mode == "decode":
        positions = jnp.broadcast_to(cache["pos"][None, None], (b, 1))
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    mrope_positions = batch.get("mrope_positions")
    if mrope_positions is not None and mode == "decode":
        mrope_positions = jnp.broadcast_to(cache["pos"][None, None, None],
                                           (3, b, 1))

    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(params, cfg, batch["enc_embeds"].astype(param_dtype))

    h, new_cache, aux = backbone(
        params, cfg, h, mode=mode, cache=cache, positions=positions,
        mrope_positions=mrope_positions, enc_out=enc_out)

    if mode == "train":
        loss = chunked_xent(params, cfg, h, batch["labels"])
        loss = loss + cfg.router_aux_weight * aux / max(cfg.n_layers, 1)
        return loss, {"aux": aux}
    if mode == "prefill":
        logits = logits_fn(params, cfg, h[:, -1:])
        return logits, new_cache
    if mode == "decode":
        logits = logits_fn(params, cfg, h)
        return logits, new_cache
    raise ValueError(mode)


def cache_logical(cfg: ModelConfig, seq_shard: bool = False):
    """Logical sharding axes mirroring ``init_cache``'s structure.

    ``seq_shard=True`` (long_500k: global_batch=1) shards the KV sequence
    dim over the data axis instead of the batch dim — sequence-parallel
    decode; XLA inserts the partial-softmax collectives.
    """
    seq = "seq" if seq_shard else None
    bat = None if seq_shard else "batch"
    if cfg.kv_seq_shard and not seq_shard:
        # split-KV decode: kv heads can't shard (MQA/GQA < tp) — put the
        # cache SEQ dim on the otherwise-idle model axis instead; XLA
        # partial-softmaxes per shard and psums the normalisers
        kv = (None, bat, "model", None, None)
    else:
        kv = (None, bat, seq, "model", None)
    if cfg.family in ("dense", "vlm", "moe"):
        return {"kv": (kv, kv), "pos": ()}
    if cfg.family == "ssm":
        per_layer = []
        for i in range(cfg.n_layers):
            if cfg.slstm_every and i % cfg.slstm_every == 0:
                per_layer.append((("batch", None),) * 3)
            else:
                per_layer.append((("batch", None, None, None),
                                  ("batch", None, "model")))
        return {"states": per_layer, "pos": ()}
    if cfg.family == "hybrid":
        # kv: [G, B, T, Hkv, hd]; ssm: ([G,A,B,H,N,P], [G,A,B,3,Dconv])
        return {"ssm": ((None, None, "batch", "model", None, None),
                        (None, None, "batch", None, None)),
                "kv": (kv, kv), "pos": ()}
    if cfg.family == "encdec":
        cross = (None, "batch", None, "model", None)
        return {"kv": (kv, kv), "cross": (cross, cross), "pos": ()}
    raise ValueError(cfg.family)


def init_cache(params, cfg: ModelConfig, batch: int, max_len: int):
    """Decode cache pytree (zeros) for one new token against a max_len
    context."""
    hd = cfg.resolved_head_dim
    pos = jnp.asarray(max_len - 1, jnp.int32)
    if cfg.family in ("dense", "vlm", "moe"):
        kv = A.init_kv_cache(cfg, batch, max_len, cfg.n_layers)
        return {"kv": kv, "pos": pos}
    if cfg.family == "ssm":
        states = []
        for i in range(cfg.n_layers):
            if cfg.slstm_every and i % cfg.slstm_every == 0:
                states.append(S.init_slstm_state(cfg, batch))
            else:
                states.append(S.init_ssm_state(cfg, batch))
        return {"states": states, "pos": pos}
    if cfg.family == "hybrid":
        groups = cfg.n_layers // cfg.attn_every
        d, di, h, hp, n = S._m2_dims(cfg)
        ssm = (jnp.zeros((groups, cfg.attn_every, batch, h, n, hp),
                         jnp.float32),
               jnp.zeros((groups, cfg.attn_every, batch, 3, di + 2 * n),
                         jnp.bfloat16))
        kv_shape = (groups, batch, max_len, cfg.n_kv_heads, hd)
        return {"ssm": ssm,
                "kv": (jnp.zeros(kv_shape, jnp.bfloat16),
                       jnp.zeros(kv_shape, jnp.bfloat16)),
                "pos": pos}
    if cfg.family == "encdec":
        kv = A.init_kv_cache(cfg, batch, max_len, cfg.n_layers)
        cross_shape = (cfg.n_layers, batch, cfg.enc_seq, cfg.n_kv_heads, hd)
        cross = (jnp.zeros(cross_shape, jnp.bfloat16),
                 jnp.zeros(cross_shape, jnp.bfloat16))
        return {"kv": kv, "cross": cross, "pos": pos}
    raise ValueError(cfg.family)
