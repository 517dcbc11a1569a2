"""Decoder-only transformer family: dense (gemma3/internlm2/deepseek-7b/
qwen2), MoE with MLA (deepseek-v2-*), and VLM backbone (qwen2-vl).

Layers are *stacked* (leading L axis) and applied with jax.lax.scan so the
HLO stays one-block-sized — essential for 60-layer dry-run compiles.
Per-layer heterogeneity (gemma3 5:1 local:global attention, per-layer
RoPE theta) rides the scan as per-layer scalar arrays.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L

Pytree = Any
NEG_BIG = 1 << 30


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _layer_init(key, cfg: ArchConfig, moe: bool):
    ks = jax.random.split(key, 4)
    p = {"attn_norm": L.rms_norm_init(cfg.d_model),
         "ffn_norm": L.rms_norm_init(cfg.d_model)}
    if cfg.kv_lora_rank:
        p["attn"] = L.mla_init(ks[0], cfg.d_model, cfg.n_heads,
                               cfg.kv_lora_rank, cfg.q_lora_rank,
                               cfg.qk_nope_dim, cfg.qk_rope_dim,
                               cfg.v_head_dim)
    else:
        p["attn"] = L.gqa_init(ks[0], cfg.d_model, cfg.n_heads,
                               cfg.n_kv_heads, cfg.hd, cfg.qkv_bias)
    if moe:
        p["moe"] = L.moe_init(ks[1], cfg.d_model, cfg.moe_d_ff,
                              cfg.n_experts, cfg.n_shared_experts,
                              cfg.n_held)
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff)
    return p


def init_params(key: jax.Array, cfg: ArchConfig) -> Pytree:
    ks = jax.random.split(key, 5)
    n_moe = cfg.n_layers - cfg.first_dense_layers if cfg.n_experts else 0
    n_dense = cfg.n_layers - n_moe

    params = {
        "embed": {"table": L.embed_init(ks[0], (cfg.vocab, cfg.d_model))},
        "final_norm": L.rms_norm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"table": L.embed_init(
            ks[1], (cfg.vocab, cfg.d_model))}

    if n_dense:
        dk = jax.random.split(ks[2], n_dense)
        params["layers"] = jax.vmap(
            lambda k: _layer_init(k, cfg, moe=False))(dk)
    if n_moe:
        mk = jax.random.split(ks[3], n_moe)
        params["moe_layers"] = jax.vmap(
            lambda k: _layer_init(k, cfg, moe=True))(mk)
    return params


# ---------------------------------------------------------------------------
# Per-layer attention pattern (gemma3 local:global etc.)
# ---------------------------------------------------------------------------


def layer_windows(cfg: ArchConfig, n: int):
    """(window[i], theta[i]) arrays for layers 0..n-1."""
    wins, thetas = [], []
    for i in range(n):
        is_global = (cfg.global_every == 0
                     or (i + 1) % (cfg.global_every + 1) == 0)
        if cfg.sliding_window and not is_global:
            wins.append(cfg.sliding_window)
            thetas.append(cfg.rope_theta)
        else:
            wins.append(NEG_BIG)  # effectively full attention
            thetas.append(cfg.rope_theta_global or cfg.rope_theta)
    return (jnp.asarray(wins, jnp.int32), jnp.asarray(thetas, jnp.float32))


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------


def yarn_of(cfg: ArchConfig) -> Optional[L.Yarn]:
    if not cfg.yarn_factor:
        return None
    return L.Yarn(cfg.yarn_factor, cfg.yarn_original_max_pos,
                  cfg.yarn_beta_fast, cfg.yarn_beta_slow, cfg.yarn_mscale,
                  cfg.yarn_mscale_all_dim)


def _block(cfg: ArchConfig, moe: bool, x, lp, positions, window, theta,
           chunk_kv, mrope_positions):
    with jax.named_scope("attention"):
        h = L.rms_norm(lp["attn_norm"], x)
        if cfg.kv_lora_rank:
            attn_out, kv = L.mla_apply(
                lp["attn"], h, positions, cfg.n_heads, cfg.kv_lora_rank,
                cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
                rope_theta=cfg.rope_theta, chunk_kv=chunk_kv,
                yarn=yarn_of(cfg))
        else:
            attn_out, kv = L.gqa_apply(
                lp["attn"], h, positions, cfg.n_heads, cfg.n_kv_heads,
                cfg.hd, window=window, causal=True, rope_theta=theta,
                chunk_kv=chunk_kv, mrope_positions=mrope_positions,
                mrope_sections=cfg.mrope_sections
                if mrope_positions is not None else None)
        x = x + attn_out
    if moe:
        h = L.rms_norm(lp["ffn_norm"], x)
        ffn_out, aux = L.moe_apply(lp["moe"], h, cfg.n_experts, cfg.top_k)
    else:
        with jax.named_scope("mlp"):
            h = L.rms_norm(lp["ffn_norm"], x)
            ffn_out, aux = L.mlp_apply(lp["mlp"], h, cfg.act), 0.0
    return x + ffn_out, kv, aux


def forward(params: Pytree, cfg: ArchConfig, tokens: jax.Array,
            vis_embeds: Optional[jax.Array] = None,
            chunk_kv: Optional[int] = None,
            collect_cache: bool = False):
    """tokens: (B, S_text). vis_embeds: (B, S_vis, D) stub patch embeds
    (VLM); they are prepended, total S = S_vis + S_text.

    Returns (logits, aux_loss) or (logits, aux_loss, cache).
    """
    with jax.named_scope("embed_head"):
        x = L.embed_lookup(params["embed"]["table"], tokens)
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    mrope_positions = None
    if vis_embeds is not None:
        x = jnp.concatenate([vis_embeds.astype(x.dtype), x], axis=1)
        B, S, _ = x.shape
        S_vis = vis_embeds.shape[1]
        side = max(int(S_vis ** 0.5), 1)
        # vision: t=0, (h, w) grid; text: t advances from side
        t = jnp.concatenate([jnp.zeros((S_vis,), jnp.int32),
                             side + jnp.arange(S - S_vis)])
        hpos = jnp.concatenate([jnp.arange(S_vis) // side,
                                side + jnp.arange(S - S_vis)])
        wpos = jnp.concatenate([jnp.arange(S_vis) % side,
                                side + jnp.arange(S - S_vis)])
        mrope_positions = jnp.broadcast_to(
            jnp.stack([t, hpos, wpos])[:, None, :],
            (3, B, S)).astype(jnp.int32)
        # positions for masking still linear
    B, S, _ = x.shape
    positions = jnp.arange(S)
    if chunk_kv is None and cfg.attn_chunk and S > cfg.attn_chunk:
        chunk_kv = cfg.attn_chunk

    aux_total = jnp.float32(0.0)
    caches = {}

    def run_stack(x, stacked, n, moe, aux_total):
        wins, thetas = layer_windows(cfg, cfg.n_layers)
        off = 0 if not moe else cfg.first_dense_layers
        wins = jax.lax.dynamic_slice_in_dim(wins, off, n)
        thetas = jax.lax.dynamic_slice_in_dim(thetas, off, n)

        def body(carry, xs):
            x, aux = carry
            lp, w, th = xs
            blk = _block
            if cfg.remat:
                blk = jax.checkpoint(
                    _block, static_argnums=(0, 1, 7),
                    policy=jax.checkpoint_policies.nothing_saveable)
            x, kv, a = blk(cfg, moe, x, lp, positions, w, th,
                           chunk_kv, mrope_positions)
            ys = kv if collect_cache else None
            return (x, aux + a), ys

        (x, aux_total), kvs = jax.lax.scan(
            body, (x, aux_total), (stacked, wins, thetas),
            unroll=cfg.scan_unroll)
        return x, aux_total, kvs

    if "layers" in params:
        n_dense = jax.tree_util.tree_leaves(
            params["layers"])[0].shape[0]
        x, aux_total, kvs = run_stack(x, params["layers"], n_dense,
                                      False, aux_total)
        if collect_cache:
            caches["dense"] = kvs
    if "moe_layers" in params:
        n_moe = jax.tree_util.tree_leaves(
            params["moe_layers"])[0].shape[0]
        x, aux_total, kvs = run_stack(x, params["moe_layers"], n_moe,
                                      True, aux_total)
        if collect_cache:
            caches["moe"] = kvs

    with jax.named_scope("embed_head"):
        x = L.rms_norm(params["final_norm"], x)
        head = params.get("lm_head", params["embed"])["table"]
        logits = L.unembed(head, x)
    if cfg.logit_sharding:
        logits = jax.lax.with_sharding_constraint(
            logits, jax.sharding.PartitionSpec(*cfg.logit_sharding))
    if collect_cache:
        return logits, aux_total, caches
    return logits, aux_total


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def lm_loss(outputs, batch):
    """Next-token CE. outputs = (logits, aux); batch['tokens'] (B, S)."""
    logits, aux = outputs[0], outputs[1]
    tokens = batch["tokens"]
    S_txt = tokens.shape[1]
    logits = logits[:, -S_txt:]  # VLM: score only the text tail
    lg = logits[:, :-1].astype(jnp.float32)
    tgt = tokens[:, 1:]
    # logsumexp form: only (B, S) temporaries besides the logits
    lse = jax.nn.logsumexp(lg, axis=-1)
    at = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    nll = lse - at
    return jnp.mean(nll) + 0.01 * aux


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------


def _local_global_split(cfg: ArchConfig):
    """gemma3-style pattern: 1 global per (global_every + 1) layers.
    Returns (plen, n_groups, n_tail): groups of plen = global_every
    local + 1 global; tail layers are all local."""
    plen = cfg.global_every + 1
    n_groups = cfg.n_layers // plen
    return plen, n_groups, cfg.n_layers - n_groups * plen


def init_cache_windowed(cfg: ArchConfig, batch: int, max_seq: int,
                        dtype=jnp.bfloat16) -> Pytree:
    """Ring-buffer caches (size = sliding_window) for local layers; full
    caches only for the global layers. For gemma3-4b @ 500k this cuts
    cache bytes ~5.6x (28 local layers hold 1024 keys instead of 524288)
    — docs/DESIGN.md §7."""
    W = min(cfg.sliding_window, max_seq)
    plen, n_groups, n_tail = _local_global_split(cfg)
    n_loc = plen - 1
    kv = lambda *shape: jnp.zeros(shape, dtype)
    cache = {
        "loc_k": kv(n_groups, n_loc, batch, W, cfg.n_kv_heads, cfg.hd),
        "loc_v": kv(n_groups, n_loc, batch, W, cfg.n_kv_heads, cfg.hd),
        "loc_pos": jnp.full((n_groups, n_loc, W), -NEG_BIG, jnp.int32),
        "glob_k": kv(n_groups, batch, max_seq, cfg.n_kv_heads, cfg.hd),
        "glob_v": kv(n_groups, batch, max_seq, cfg.n_kv_heads, cfg.hd),
    }
    if n_tail:
        cache["tail_k"] = kv(n_tail, batch, W, cfg.n_kv_heads, cfg.hd)
        cache["tail_v"] = kv(n_tail, batch, W, cfg.n_kv_heads, cfg.hd)
        cache["tail_pos"] = jnp.full((n_tail, W), -NEG_BIG, jnp.int32)
    return cache


def decode_step_windowed(params: Pytree, cfg: ArchConfig, cache: Pytree,
                         token: jax.Array, pos: jax.Array):
    """One-token decode with ring-buffer local caches (gemma3 pattern).
    Layers are re-grouped (global_every local + 1 global) x n_groups +
    a local tail; parameters are reshaped views of the (L, ...) stacks."""
    B = token.shape[0]
    W = cache["loc_k"].shape[3]
    plen, n_groups, n_tail = _local_global_split(cfg)
    n_loc = plen - 1
    x = L.embed_lookup(params["embed"]["table"], token[:, None])
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    positions = pos[None]
    theta_l = cfg.rope_theta
    theta_g = cfg.rope_theta_global or cfg.rope_theta

    stacked = params["layers"]
    grouped = jax.tree_util.tree_map(
        lambda a: a[: n_groups * plen].reshape(
            (n_groups, plen) + a.shape[1:]), stacked)
    tail = jax.tree_util.tree_map(lambda a: a[n_groups * plen:], stacked)

    def attn_ring(lp, h, kc, vc, kpos, theta):
        slot = pos % W
        k_new = L.masked_dense_apply(h, lp["attn"]["w_k"]).reshape(
            B, 1, cfg.n_kv_heads, cfg.hd)
        v_new = L.masked_dense_apply(h, lp["attn"]["w_v"]).reshape(
            B, 1, cfg.n_kv_heads, cfg.hd)
        k_new = L.apply_rope(k_new, positions, theta)
        kc = jax.lax.dynamic_update_slice(
            kc, k_new.astype(kc.dtype), (0, slot, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            vc, v_new.astype(vc.dtype), (0, slot, 0, 0))
        kpos = jax.lax.dynamic_update_slice(kpos, pos[None], (slot,))
        out, _ = L.gqa_apply(lp["attn"], h, positions, cfg.n_heads,
                             cfg.n_kv_heads, cfg.hd,
                             window=cfg.sliding_window, causal=True,
                             rope_theta=theta, kv_override=(kc, vc),
                             k_positions=kpos)
        return out, kc, vc, kpos

    def attn_full(lp, h, kc, vc, theta):
        k_new = L.masked_dense_apply(h, lp["attn"]["w_k"]).reshape(
            B, 1, cfg.n_kv_heads, cfg.hd)
        v_new = L.masked_dense_apply(h, lp["attn"]["w_v"]).reshape(
            B, 1, cfg.n_kv_heads, cfg.hd)
        k_new = L.apply_rope(k_new, positions, theta)
        kc = jax.lax.dynamic_update_slice(
            kc, k_new.astype(kc.dtype), (0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            vc, v_new.astype(vc.dtype), (0, pos, 0, 0))
        out, _ = L.gqa_apply(lp["attn"], h, positions, cfg.n_heads,
                             cfg.n_kv_heads, cfg.hd, window=None,
                             causal=True, rope_theta=theta,
                             kv_override=(kc, vc),
                             k_positions=jnp.arange(kc.shape[1]))
        return out, kc, vc

    def ffn(lp, x):
        h = L.rms_norm(lp["ffn_norm"], x)
        return x + L.mlp_apply(lp["mlp"], h, cfg.act)

    def group_body(x, xs):
        gp, lk, lv, lpos, gk, gv = xs
        nlk, nlv, nlpos = [], [], []
        for i in range(plen):
            lp = jax.tree_util.tree_map(lambda a: a[i], gp)
            h = L.rms_norm(lp["attn_norm"], x)
            if i < n_loc:
                out, k2, v2, p2 = attn_ring(lp, h, lk[i], lv[i],
                                            lpos[i], theta_l)
                nlk.append(k2)
                nlv.append(v2)
                nlpos.append(p2)
            else:
                out, gk, gv = attn_full(lp, h, gk, gv, theta_g)
            x = ffn(lp, x + out)
        return x, (jnp.stack(nlk), jnp.stack(nlv), jnp.stack(nlpos),
                   gk, gv)

    x, (lks, lvs, lposs, gks, gvs) = jax.lax.scan(
        group_body, x, (grouped, cache["loc_k"], cache["loc_v"],
                        cache["loc_pos"], cache["glob_k"],
                        cache["glob_v"]))
    new_cache = dict(cache, loc_k=lks, loc_v=lvs, loc_pos=lposs,
                     glob_k=gks, glob_v=gvs)

    if n_tail:
        def tail_body(x, xs):
            lp, kc, vc, kpos = xs
            h = L.rms_norm(lp["attn_norm"], x)
            out, k2, v2, p2 = attn_ring(lp, h, kc, vc, kpos, theta_l)
            x = ffn(lp, x + out)
            return x, (k2, v2, p2)

        x, (tk, tv, tp) = jax.lax.scan(
            tail_body, x, (tail, cache["tail_k"], cache["tail_v"],
                           cache["tail_pos"]))
        new_cache.update(tail_k=tk, tail_v=tv, tail_pos=tp)

    x = L.rms_norm(params["final_norm"], x)
    head = params.get("lm_head", params["embed"])["table"]
    logits = L.unembed(head, x)[:, 0]
    return logits, new_cache


def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> Pytree:
    n_moe = cfg.n_layers - cfg.first_dense_layers if cfg.n_experts else 0
    n_dense = cfg.n_layers - n_moe
    if cfg.kv_lora_rank:
        mk = lambda n: {
            "c_kv": jnp.zeros((n, batch, max_seq, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((n, batch, max_seq, 1, cfg.qk_rope_dim),
                                dtype)}
    else:
        mk = lambda n: {
            "k": jnp.zeros((n, batch, max_seq, cfg.n_kv_heads, cfg.hd),
                           dtype),
            "v": jnp.zeros((n, batch, max_seq, cfg.n_kv_heads, cfg.hd),
                           dtype)}
    out = {}
    if n_dense:
        out["dense"] = mk(n_dense)
    if n_moe:
        out["moe"] = mk(n_moe)
    return out


def decode_step(params: Pytree, cfg: ArchConfig, cache: Pytree,
                token: jax.Array, pos: jax.Array):
    """One-token decode. token: (B,) int32; pos: scalar int32 (current
    position; cache holds keys for positions < pos... <= pos after write).

    Returns (logits (B, V), new_cache).
    """
    B = token.shape[0]
    x = L.embed_lookup(params["embed"]["table"], token[:, None])
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    positions = pos[None]  # (1,)

    wins, thetas = layer_windows(cfg, cfg.n_layers)
    new_cache = {}

    def attn_gqa(lp, h, lc, w, th):
        # project new kv, write into cache at pos, attend over cache
        k_new = L.masked_dense_apply(h, lp["attn"]["w_k"]).reshape(
            B, 1, cfg.n_kv_heads, cfg.hd)
        v_new = L.masked_dense_apply(h, lp["attn"]["w_v"]).reshape(
            B, 1, cfg.n_kv_heads, cfg.hd)
        if "bias_k" in lp["attn"]:
            k_new = k_new + lp["attn"]["bias_k"].reshape(
                cfg.n_kv_heads, cfg.hd).astype(k_new.dtype)
            v_new = v_new + lp["attn"]["bias_v"].reshape(
                cfg.n_kv_heads, cfg.hd).astype(v_new.dtype)
        k_new = L.apply_rope(k_new, positions, th)
        kc = jax.lax.dynamic_update_slice(
            lc["k"], k_new.astype(lc["k"].dtype), (0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            lc["v"], v_new.astype(lc["v"].dtype), (0, pos, 0, 0))
        S_max = kc.shape[1]
        k_pos = jnp.arange(S_max)
        out, _ = L.gqa_apply(
            lp["attn"], h, positions, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
            window=w, causal=True, rope_theta=th,
            kv_override=(kc, vc), k_positions=k_pos)
        return out, {"k": kc, "v": vc}

    def attn_mla(lp, h, lc):
        dkv = L.masked_dense_apply(h, lp["attn"]["w_dkv"])
        c_kv_new = L.rms_norm({"scale": lp["attn"]["kv_norm_scale"]},
                              dkv[..., :cfg.kv_lora_rank])
        k_rope_new = L.apply_rope(
            dkv[..., cfg.kv_lora_rank:][:, :, None, :], positions,
            cfg.rope_theta, yarn_of(cfg))
        ckv = jax.lax.dynamic_update_slice(
            lc["c_kv"], c_kv_new.astype(lc["c_kv"].dtype), (0, pos, 0))
        krp = jax.lax.dynamic_update_slice(
            lc["k_rope"], k_rope_new.astype(lc["k_rope"].dtype),
            (0, pos, 0, 0))
        out, _ = L.mla_apply(
            lp["attn"], h, positions, cfg.n_heads, cfg.kv_lora_rank,
            cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
            rope_theta=cfg.rope_theta, cache_kv=(ckv, krp),
            yarn=yarn_of(cfg))
        return out, {"c_kv": ckv, "k_rope": krp}

    def run_stack(x, stacked, cache_part, moe, offset):
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        w = jax.lax.dynamic_slice_in_dim(wins, offset, n)
        th = jax.lax.dynamic_slice_in_dim(thetas, offset, n)

        def body(x, xs):
            lp, lc, wi, thi = xs
            h = L.rms_norm(lp["attn_norm"], x)
            if cfg.kv_lora_rank:
                attn_out, nc = attn_mla(lp, h, lc)
            else:
                attn_out, nc = attn_gqa(lp, h, lc, wi, thi)
            x = x + attn_out
            h = L.rms_norm(lp["ffn_norm"], x)
            if moe:
                ffn_out, _ = L.moe_apply(lp["moe"], h, cfg.n_experts,
                                         cfg.top_k)
            else:
                ffn_out = L.mlp_apply(lp["mlp"], h, cfg.act)
            return x + ffn_out, nc

        return jax.lax.scan(body, x, (stacked, cache_part, w, th),
                            unroll=cfg.scan_unroll)

    off = 0
    if "layers" in params:
        x, nc = run_stack(x, params["layers"], cache["dense"], False, 0)
        new_cache["dense"] = nc
        off = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    if "moe_layers" in params:
        x, nc = run_stack(x, params["moe_layers"], cache["moe"], True, off)
        new_cache["moe"] = nc

    x = L.rms_norm(params["final_norm"], x)
    head = params.get("lm_head", params["embed"])["table"]
    logits = L.unembed(head, x)[:, 0]
    return logits, new_cache
