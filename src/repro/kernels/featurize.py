"""Pallas TPU kernel: fused featurization (scaler + one-hot + concat).

The paper's §7.4 identifies relational→model data conversion as a main
PREDICT overhead. On TPU we fuse the whole featurization into one VMEM pass:
a row-block of raw numeric columns and categorical codes enters VMEM once and
the full feature block (numerics scaled, categoricals one-hot, concatenated)
leaves — no intermediate HBM materialization per featurizer op.

Categorical segments are static (compile-time python loop), so each one-hot
writes to a statically-sliced column range of the output block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES


def _kernel(
    num_ref, cat_ref, off_ref, sc_ref, vals_ref, o_ref, *, segments, n_num
):
    if n_num:
        num = num_ref[...]  # (BN, Kn)
        o_ref[:, :n_num] = (num - off_ref[0][None, :]) * sc_ref[0][None, :]
    if segments:
        cat = cat_ref[...]  # (BN, Kc) int32
        col = n_num
        for j, (start, length) in enumerate(segments):
            vals = vals_ref[0, start : start + length]  # (V_j,) static slice
            oh = (cat[:, j : j + 1] == vals[None, :]).astype(jnp.float32)
            o_ref[:, col : col + length] = oh
            col += length


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def featurize(
    num: jnp.ndarray,
    cat: jnp.ndarray,
    offset: jnp.ndarray,
    scale: jnp.ndarray,
    cat_values: jnp.ndarray,
    cat_segments: tuple[tuple[int, int], ...],
    *,
    block_n: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """num:(N,Kn) f32; cat:(N,Kc) int32; offset/scale:(Kn,);
    cat_values:(Vtot,) concatenated category values (int32);
    cat_segments: ((start,len), ...) per categorical column.
    Returns (N, Kn + Vtot) f32. Rows are padded internally to a multiple of
    ``block_n`` (categorical pad code -1 never matches a category) and
    cropped back, so callers pass natural row counts."""
    N, Kn = num.shape
    Kc = cat.shape[1]
    Vtot = int(cat_values.shape[0])
    Fout = Kn + Vtot
    if Fout == 0:
        return jnp.zeros((N, 0), jnp.float32)
    Np = _round_up(max(N, 1), block_n)
    num = jnp.pad(num.astype(jnp.float32), ((0, Np - N), (0, 0)))
    cat = jnp.pad(cat.astype(jnp.int32), ((0, Np - N), (0, 0)), constant_values=-1)
    offset = offset.astype(jnp.float32)
    scale = scale.astype(jnp.float32)
    cat_values = cat_values.astype(jnp.int32)
    # Zero-width operands break Pallas block indexing; widen them to one
    # inert column. The kernel never reads it: n_num / segments are static
    # and skip the padded operand entirely.
    if Kn == 0:
        num = jnp.zeros((Np, 1), jnp.float32)
        offset = scale = jnp.zeros((1,), jnp.float32)
    if Kc == 0:
        cat = jnp.full((Np, 1), -1, jnp.int32)
    if Vtot == 0:
        cat_values = jnp.zeros((1,), jnp.int32)
    Knp, Kcp, Vp = max(Kn, 1), max(Kc, 1), max(Vtot, 1)
    grid = (Np // block_n,)
    out = pl.pallas_call(
        functools.partial(_kernel, segments=tuple(cat_segments), n_num=Kn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, Knp), lambda n: (n, 0)),
            pl.BlockSpec((block_n, Kcp), lambda n: (n, 0)),
            pl.BlockSpec((1, Knp), lambda n: (0, 0)),
            pl.BlockSpec((1, Knp), lambda n: (0, 0)),
            pl.BlockSpec((1, Vp), lambda n: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, Fout), lambda n: (n, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, Fout), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="featurize",
    )(
        num,
        cat,
        offset.reshape(1, -1),
        scale.reshape(1, -1),
        cat_values.reshape(1, -1),
    )
    return out[:N]
