"""Transformer stacks: dense / MoE decoders, encoder, enc-dec composition.

Layer stacks scan over stacked params (lax.scan with the param tree as the
scanned xs) with optional remat — one traced body regardless of depth, which
is what keeps the 126-layer llama3-405b dry-run compile tractable and bounds
live activations.

Vocab-sharded embedding lookups use a shard_map masked-gather + psum over the
``model`` axis (Megatron-style) when a mesh is provided; logits/loss keep the
vocab dimension sharded end-to-end (the chunked cross-entropy reduces over
the sharded vocab axis with an automatic psum).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models.base import ArchConfig, fsdp_axes
from repro.models.moe import moe_ffn, moe_param_shapes


# ---------------------------------------------------------------------------
# Param shape trees
# ---------------------------------------------------------------------------


def attn_param_shapes(cfg: ArchConfig) -> dict:
    D, H, KH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    s = {
        "wq_col": (D, H * hd),
        "wk_col": (D, KH * hd),
        "wv_col": (D, KH * hd),
        "wo_row": (H * hd, D),
    }
    if cfg.qkv_bias:
        s.update({"bq_col": (H * hd,), "bk_col": (KH * hd,), "bv_col": (KH * hd,)})
    return s


def mlp_param_shapes(cfg: ArchConfig, d_ff: Optional[int] = None) -> dict:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp_act == "silu_gated":
        return {"wg_col": (D, F), "wu_col": (D, F), "wd_row": (F, D)}
    return {"wu_col": (D, F), "wd_row": (F, D)}


def decoder_layer_shapes(cfg: ArchConfig, cross: bool = False) -> dict:
    s: dict[str, Any] = {
        "ln1": (cfg.d_model,),
        "ln2": (cfg.d_model,),
        "attn": attn_param_shapes(cfg),
    }
    if cross:
        s["ln_x"] = (cfg.d_model,)
        s["xattn"] = attn_param_shapes(cfg)
    if cfg.family == "moe":
        s["moe"] = moe_param_shapes(cfg)
    else:
        s["mlp"] = mlp_param_shapes(cfg)
    return s


def stack_shapes(layer_shapes: dict, n: int) -> dict:
    def rec(t):
        if isinstance(t, dict):
            return {k: rec(v) for k, v in t.items()}
        return (n, *t)

    return rec(layer_shapes)


# ---------------------------------------------------------------------------
# Embedding with vocab sharding
# ---------------------------------------------------------------------------


def embed_lookup(embed: jnp.ndarray, tokens: jnp.ndarray, mesh) -> jnp.ndarray:
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return jnp.take(embed, tokens, axis=0)
    ax = fsdp_axes(mesh)
    # batch stays replicated when it doesn't divide the data axes (e.g. the
    # B=1 long_500k decode cells) — vocab sharding over `model` still applies.
    dsz = int(
        np.prod(
            [
                mesh.shape[a]
                for a in (ax.data if isinstance(ax.data, tuple) else (ax.data,))
            ]
        )
    )
    b_ax = ax.data if tokens.shape[0] % dsz == 0 else None

    def local(e, t):  # e: (V/m, D) local shard; t: (B/d, S) local batch
        Vl = e.shape[0]
        lo = jax.lax.axis_index("model") * Vl
        ids = t - lo
        ok = (ids >= 0) & (ids < Vl)
        out = jnp.take(e, jnp.clip(ids, 0, Vl - 1), axis=0)
        out = jnp.where(ok[..., None], out, jnp.zeros((), e.dtype))
        return jax.lax.psum(out, "model")

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model", None), P(b_ax, None)),
        out_specs=P(b_ax, None, None),
        check_vma=False,
    )(embed, tokens)


# ---------------------------------------------------------------------------
# Decoder stack (dense or MoE), scan-over-layers, train/prefill/decode modes
# ---------------------------------------------------------------------------


def _layer_fwd(lp: dict, h: jnp.ndarray, cfg: ArchConfig, positions, causal, window):
    a = L.attn_block(
        lp["attn"], L.rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg,
        positions=positions, causal=causal, window=window,
    )
    h = h + a
    hn = L.rmsnorm(h, lp["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        m = moe_ffn(lp["moe"], hn, cfg)
    else:
        m = L.mlp_block(lp["mlp"], hn, cfg)
    return h + m


def decoder_forward(
    layers_params: dict,
    h: jnp.ndarray,
    cfg: ArchConfig,
    *,
    positions: jnp.ndarray,
    causal: bool = True,
    window: int = 0,
    mesh=None,
) -> jnp.ndarray:
    from repro.models.layers import seq_gather, seq_shard

    def body(carry, lp):
        # gather seq at entry (clean Megatron layouts inside the block),
        # re-shard at exit (remat-saved carries are 1/TP-size)
        carry = seq_gather(carry, cfg, mesh)
        out = _layer_fwd(lp, carry, cfg, positions, causal, window)
        return seq_shard(out, cfg, mesh), None

    if cfg.remat:
        body = jax.checkpoint(body)
    h = seq_shard(h, cfg, mesh)
    h, _ = jax.lax.scan(body, h, layers_params)
    return h


def decoder_prefill(
    layers_params: dict,
    h: jnp.ndarray,
    cfg: ArchConfig,
    *,
    positions: jnp.ndarray,
    cache_len: int,
    window: int = 0,
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
    """Forward + emit per-layer K/V caches padded to cache_len."""
    B, S, _ = h.shape
    KH, hd = cfg.n_kv_heads, cfg.hd

    def body(carry, lp):
        hh = carry
        hn = L.rmsnorm(hh, lp["ln1"], cfg.norm_eps)
        q, k, v = L.attn_proj_qkv(lp["attn"], hn, cfg)
        if cfg.rope_theta > 0:
            q = L.rope(q, positions, cfg.rope_theta)
            k = L.rope(k, positions, cfg.rope_theta)
        # caches keep the original KH heads; expansion is attention-local
        qe, ke, ve, Hr = L.expand_heads_for_tp(q, k, v, cfg)
        att = L.attention_chunked(qe, ke, ve, causal=True, window=window)
        att = att[:, :, :Hr].reshape(B, S, cfg.n_heads * hd)
        hh = hh + jnp.einsum("bsh,hd->bsd", att, lp["attn"]["wo_row"])
        hn2 = L.rmsnorm(hh, lp["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            m = moe_ffn(lp["moe"], hn2, cfg)
        else:
            m = L.mlp_block(lp["mlp"], hn2, cfg)
        kc = jnp.pad(k, ((0, 0), (0, cache_len - S), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, cache_len - S), (0, 0), (0, 0)))
        return hh + m, (kc, vc)

    if cfg.remat:
        body = jax.checkpoint(body)
    h, (kcs, vcs) = jax.lax.scan(body, h, layers_params)
    return h, (kcs, vcs)


def decoder_decode_step(
    layers_params: dict,
    h: jnp.ndarray,  # (B, D) one token's hidden
    kv_caches: tuple[jnp.ndarray, jnp.ndarray],  # (L,B,S,KH,hd) ×2
    lengths: jnp.ndarray,  # (B,)
    cfg: ArchConfig,
    *,
    window: int = 0,
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
    B = h.shape[0]
    KH, hd = cfg.n_kv_heads, cfg.hd
    pos = lengths  # 0-based position of the new token

    def body(carry, xs):
        hh = carry
        lp, kc, vc = xs
        hn = L.rmsnorm(hh, lp["ln1"], cfg.norm_eps)[:, None, :]  # (B,1,D)
        q, k, v = L.attn_proj_qkv(lp["attn"], hn, cfg)
        if cfg.rope_theta > 0:
            q = L.rope(q, pos[:, None], cfg.rope_theta)
            k = L.rope(k, pos[:, None], cfg.rope_theta)
        kc = kc.at[jnp.arange(B), pos].set(k[:, 0])
        vc = vc.at[jnp.arange(B), pos].set(v[:, 0])
        att = L.attention_decode(q[:, 0], kc, vc, lengths + 1, window=window)
        hh = hh + jnp.einsum("bh,hd->bd", att.reshape(B, -1), lp["attn"]["wo_row"])
        hn2 = L.rmsnorm(hh, lp["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            m = moe_ffn(lp["moe"], hn2[:, None, :], cfg)[:, 0]
        else:
            m = L.mlp_block(lp["mlp"], hn2[:, None, :], cfg)[:, 0]
        return hh + m, (kc, vc)

    h, (kcs, vcs) = jax.lax.scan(body, h, (layers_params, *kv_caches))
    return h, (kcs, vcs)


# ---------------------------------------------------------------------------
# Encoder stack (whisper) + cross-attention decoder
# ---------------------------------------------------------------------------


def encoder_forward(layers_params, h, cfg: ArchConfig, positions):
    def body(carry, lp):
        a = L.attn_block(
            lp["attn"], L.rmsnorm(carry, lp["ln1"], cfg.norm_eps), cfg,
            positions=positions, causal=False,
        )
        hh = carry + a
        m = L.mlp_block(lp["mlp"], L.rmsnorm(hh, lp["ln2"], cfg.norm_eps), cfg)
        return hh + m, None

    if cfg.remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, layers_params)
    return h


def encdec_decoder_forward(
    layers_params, h, enc_out, cfg: ArchConfig, *, positions, enc_positions
):
    """Decoder with self-attn + cross-attn (training / scoring path)."""
    B, S, _ = h.shape

    def body(carry, lp):
        hh = carry
        a = L.attn_block(
            lp["attn"], L.rmsnorm(hh, lp["ln1"], cfg.norm_eps), cfg,
            positions=positions, causal=True,
        )
        hh = hh + a
        # cross-attention: keys/values from encoder output
        hn = L.rmsnorm(hh, lp["ln_x"], cfg.norm_eps)
        _, xk, xv = L.attn_proj_qkv(lp["xattn"], enc_out, cfg)
        q = jnp.einsum("bsd,dh->bsh", hn, lp["xattn"]["wq_col"])
        if cfg.qkv_bias:
            q = q + lp["xattn"]["bq_col"]
        q = q.reshape(B, S, cfg.n_heads, cfg.hd)
        att = L.attention_chunked(q, xk, xv, causal=False)
        att = att.reshape(B, S, cfg.n_heads * cfg.hd)
        hh = hh + jnp.einsum("bsh,hd->bsd", att, lp["xattn"]["wo_row"])
        m = L.mlp_block(lp["mlp"], L.rmsnorm(hh, lp["ln2"], cfg.norm_eps), cfg)
        return hh + m, None

    if cfg.remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, layers_params)
    return h
