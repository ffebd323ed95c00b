"""Pure-jnp oracles for every Pallas kernel (CPU-checkable ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def tree_gemm_ref(x, A, B, C, D, V, base: float) -> jnp.ndarray:
    """GEMM-strategy tree inference. x:(N,F); A:(T,F,I); B:(T,I); C:(T,I,L);
    D:(T,L); V:(T,L) -> (N,) raw scores."""
    hi = jax.lax.Precision.HIGHEST
    S = jnp.einsum("nf,tfi->nti", x.astype(jnp.float32), A, precision=hi)
    dec = (S <= B[None]).astype(jnp.float32)
    P = jnp.einsum("nti,til->ntl", dec, C, precision=hi)
    match = (P == D[None]).astype(jnp.float32)
    return jnp.einsum("ntl,tl->n", match, V, precision=hi) + base


def featurize_ref(num, cat, offset, scale, cat_values, cat_segments):
    """Fused scaler + one-hot + concat.

    num:(N,Kn) f32; cat:(N,Kc) int32; offset/scale:(Kn,);
    cat_values:(Vtot,) concatenated category values;
    cat_segments: list of (start, length) per categorical column.
    Output: (N, Kn + Vtot) f32, numerics first.
    """
    parts = [(num.astype(jnp.float32) - offset) * scale]
    for j, (s, l) in enumerate(cat_segments):
        vals = jax.lax.dynamic_slice_in_dim(cat_values, s, l)
        parts.append((cat[:, j : j + 1] == vals[None, :]).astype(jnp.float32))
    return jnp.concatenate(parts, axis=1)


def gather_join_ref(fk, skeys, spay):
    """Dim-table equi-join gather oracle (unique, pre-sorted dim keys).

    fk:(N,) int32 fact keys; skeys:(M,) int32 sorted unique dim keys;
    spay:(M,P) f32 payload aligned to ``skeys``. Returns ``(out, hit)`` —
    out:(N,P) f32 (zero on miss, so the oracle and the one-hot-matmul kernel
    agree bitwise on *every* row, not just hits), hit:(N,) bool.
    """
    pos = jnp.clip(jnp.searchsorted(skeys, fk), 0, skeys.shape[0] - 1)
    hit = skeys[pos] == fk
    out = jnp.where(hit[:, None], spay[pos], jnp.float32(0.0))
    return out, hit


def segment_agg_ref(vals, w, sid, *, num_segments):
    """Masked segmented aggregate oracle.

    vals:(N,C) f32; w:(N,) f32 validity weights (the fused filter mask);
    sid:(N,) int32 segment ids in ``[0, num_segments)``. Returns
    ``(counts, sums, mins, maxs)`` with the same shapes/semantics as the
    Pallas kernel: counts:(S,), sums:(S,C) weighted sums, mins/maxs:(S,C)
    masked extrema (+inf/-inf for segments with no valid rows).
    """
    S = num_segments
    wf = w.astype(jnp.float32)
    vf = vals.astype(jnp.float32)
    if S == 1:
        # global fold: plain reductions, not a scatter of N rows into one
        # slot (XLA lowers segment_* to scatter-adds, which on CPU are far
        # slower than a tree reduce)
        if vf.shape[0] == 0:
            return (
                jnp.zeros((1,), jnp.float32),
                jnp.zeros((1, vf.shape[1]), jnp.float32),
                jnp.full((1, vf.shape[1]), jnp.inf, jnp.float32),
                jnp.full((1, vf.shape[1]), -jnp.inf, jnp.float32),
            )
        valid1 = (wf > 0)[:, None]
        counts = jnp.sum(wf)[None]
        sums = jnp.sum(vf * wf[:, None], axis=0)[None]
        mins = jnp.min(jnp.where(valid1, vf, jnp.inf), axis=0)[None]
        maxs = jnp.max(jnp.where(valid1, vf, -jnp.inf), axis=0)[None]
        return counts, sums, mins, maxs
    counts = jax.ops.segment_sum(wf, sid, num_segments=S)
    sums = jax.ops.segment_sum(vf * wf[:, None], sid, num_segments=S)
    valid = (wf > 0)[:, None]
    mins = jax.ops.segment_min(
        jnp.where(valid, vf, jnp.inf), sid, num_segments=S
    )
    maxs = jax.ops.segment_max(
        jnp.where(valid, vf, -jnp.inf), sid, num_segments=S
    )
    return counts, sums, mins, maxs


def flash_attention_ref(q, k, v, causal: bool = True, scale: float | None = None):
    """Full-softmax attention oracle. q:(B,Sq,H,D) k,v:(B,Skv,KH,D) with GQA
    (H % KH == 0). Returns (B,Sq,H,D)."""
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qg = qf.reshape(B, Sq, KH, G, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf)
    if causal:
        Skv = k.shape[1]
        mask = jnp.arange(Sq)[:, None] + (Skv - Sq) >= jnp.arange(Skv)[None, :]
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, lengths, scale: float | None = None):
    """Single-token decode attention oracle.

    q:(B,H,D); k_cache,v_cache:(B,S,KH,D); lengths:(B,) valid KV lengths.
    Returns (B,H,D)."""
    B, H, D = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qg = (q.astype(jnp.float32) * scale).reshape(B, KH, G, D)
    logits = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache.astype(jnp.float32))
    mask = jnp.arange(S)[None, :] < lengths[:, None]  # (B,S)
    logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)
