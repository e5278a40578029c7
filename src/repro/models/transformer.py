"""Dense decoder-only transformer LMs.

Covers: minitron-8b, deepseek-7b, gemma-2b (MQA), gemma3-12b (5:1
local:global sliding-window pattern), and the paper's GPT-15/30/39B.

Blocks are parameter-stacked along a leading layer axis; the forward pass is a
(remat'd) ``lax.scan``.  Pattern archs (gemma3) scan over *groups* of
``ratio`` local layers + 1 global layer so window masks stay static.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models.common import (
    dense_init, embed_init, linear, rms_norm, scan_unroll, shard_act,
    softmax_cross_entropy,
)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def block_init(cfg: ArchConfig, rng, dtype) -> Params:
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": attn.attn_init(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim, dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "mlp": mlp_mod.mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.activation, dtype),
    }


def init(cfg: ArchConfig, rng, dtype=jnp.float32) -> Params:
    k_emb, k_blocks, k_head = jax.random.split(rng, 3)
    p: Params = {
        "embed": embed_init(k_emb, cfg.vocab_size, cfg.d_model, dtype),
        "blocks": jax.vmap(lambda k: block_init(cfg, k, dtype))(
            jax.random.split(k_blocks, cfg.n_layers)),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size, dtype)
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _block_apply(cfg: ArchConfig, p: Params, h: jnp.ndarray, *,
                 window: int, use_pallas: bool) -> jnp.ndarray:
    a = attn.self_attention(
        p["attn"], rms_norm(h, p["ln1"], cfg.norm_eps),
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, causal=True, window=window,
        use_pallas=use_pallas)
    h = h + a
    m = mlp_mod.mlp(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps), cfg.activation)
    h = h + m
    return shard_act(h, ("batch", "seq", "embed"))


def _scan_blocks(cfg: ArchConfig, blocks: Params, h: jnp.ndarray, *,
                 use_pallas: bool, remat: bool = True) -> jnp.ndarray:
    ratio = cfg.local_global_ratio

    if not ratio:
        def body(carry, p):
            return _block_apply(cfg, p, carry, window=cfg.sliding_window,
                                use_pallas=use_pallas), None
        body = jax.checkpoint(body) if remat else body
        h, _ = jax.lax.scan(body, h, blocks, unroll=scan_unroll())
        return h

    # pattern: [ratio local layers, 1 global layer] per group
    gsz = ratio + 1
    n_groups = cfg.n_layers // gsz
    grouped = jax.tree.map(lambda x: x.reshape(n_groups, gsz, *x.shape[1:]), blocks)

    def group_body(carry, pg):
        local = jax.tree.map(lambda x: x[:ratio], pg)
        glob = jax.tree.map(lambda x: x[ratio], pg)

        def local_body(c, p):
            return _block_apply(cfg, p, c, window=cfg.sliding_window,
                                use_pallas=use_pallas), None
        carry, _ = jax.lax.scan(local_body, carry, local)
        carry = _block_apply(cfg, glob, carry, window=0, use_pallas=use_pallas)
        return carry, None

    group_body = jax.checkpoint(group_body) if remat else group_body
    h, _ = jax.lax.scan(group_body, h, grouped, unroll=scan_unroll())
    return h


@jax.named_scope("embed")
def embed_tokens(cfg: ArchConfig, params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    from repro.models.common import act_dtype_cast
    h = act_dtype_cast(params["embed"][tokens])
    if cfg.scale_embed:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    return shard_act(h, ("batch", "seq", "embed"))


@jax.named_scope("head")
def lm_head(cfg: ArchConfig, params: Params, h: jnp.ndarray) -> jnp.ndarray:
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = linear(h, w)
    return shard_act(logits, ("batch_head", "seq", "vocab"))


def forward(cfg: ArchConfig, params: Params, batch: Dict[str, jnp.ndarray], *,
            use_pallas: bool = False, remat: bool = True):
    """-> (logits (B,T,V), aux_loss scalar)."""
    h = embed_tokens(cfg, params, batch["tokens"])
    h = _scan_blocks(cfg, params["blocks"], h, use_pallas=use_pallas, remat=remat)
    return lm_head(cfg, params, h), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, seq_len: int, dtype=jnp.bfloat16) -> Params:
    ratio = cfg.local_global_ratio
    if not ratio:
        S = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
        shape = (cfg.n_layers, batch, S, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    gsz = ratio + 1
    n_groups = cfg.n_layers // gsz
    w = cfg.sliding_window
    loc = (n_groups, ratio, batch, min(seq_len, w), cfg.n_kv_heads, cfg.head_dim)
    glb = (n_groups, batch, seq_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k_loc": jnp.zeros(loc, dtype), "v_loc": jnp.zeros(loc, dtype),
            "k_glb": jnp.zeros(glb, dtype), "v_glb": jnp.zeros(glb, dtype)}


def _decode_block(cfg: ArchConfig, p: Params, h, ck, cv, pos, window):
    a, (ck, cv) = attn.decode_self_attention(
        p["attn"], rms_norm(h, p["ln1"], cfg.norm_eps), ck, cv, pos,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, window=window)
    h = h + a
    h = h + mlp_mod.mlp(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps), cfg.activation)
    return h, ck, cv


def decode_step(cfg: ArchConfig, params: Params, cache: Params,
                tokens: jnp.ndarray, pos: jnp.ndarray):
    """tokens: (B, 1) int32; pos: scalar int32 (next position index).

    Returns (logits (B, 1, V), new_cache)."""
    h = embed_tokens(cfg, params, tokens)
    ratio = cfg.local_global_ratio

    if not ratio:
        def body(carry, inp):
            p, ck, cv = inp
            hh, ck, cv = _decode_block(cfg, p, carry, ck, cv, pos, cfg.sliding_window)
            return hh, (ck, cv)
        h, (nk, nv) = jax.lax.scan(body, h, (params["blocks"], cache["k"], cache["v"]),
                                   unroll=scan_unroll())
        new_cache = {"k": nk, "v": nv}
    else:
        gsz = ratio + 1
        n_groups = cfg.n_layers // gsz
        grouped = jax.tree.map(
            lambda x: x.reshape(n_groups, gsz, *x.shape[1:]), params["blocks"])

        def body(carry, inp):
            pg, klo, vlo, kgl, vgl = inp
            nk_l, nv_l = [], []
            for i in range(ratio):
                pl = jax.tree.map(lambda x: x[i], pg)
                carry, ck, cv = _decode_block(cfg, pl, carry, klo[i], vlo[i],
                                              pos, cfg.sliding_window)
                nk_l.append(ck)
                nv_l.append(cv)
            pglob = jax.tree.map(lambda x: x[ratio], pg)
            carry, kgl, vgl = _decode_block(cfg, pglob, carry, kgl, vgl, pos, 0)
            return carry, (jnp.stack(nk_l), jnp.stack(nv_l), kgl, vgl)

        h, (klo, vlo, kgl, vgl) = jax.lax.scan(
            body, h, (grouped, cache["k_loc"], cache["v_loc"],
                      cache["k_glb"], cache["v_glb"]), unroll=scan_unroll())
        new_cache = {"k_loc": klo, "v_loc": vlo, "k_glb": kgl, "v_glb": vgl}

    return lm_head(cfg, params, h), new_cache
