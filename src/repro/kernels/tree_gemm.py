"""Pallas TPU kernel: GEMM-strategy tree-ensemble inference.

The paper's MLtoDNN hotspot, rethought for the MXU: each (batch-block, tree)
grid step runs the fused chain

    S = X·A  →  D = (S ≤ B)  →  P = D·C  →  match = (P == Dcount)  →  y += Σ match·V

entirely in VMEM, with the two contractions on the MXU. Trees accumulate into
the output block across the innermost grid dimension (revisited output block;
init at t == 0) — no HBM round-trips between trees.

Tiling: rows are tiled by ``block_n``; F/I/L are MXU-aligned by padding in
``repro.kernels.ops`` (zero feature columns, +inf thresholds, zero path
columns and Dcount = -1 are all provably inert — see ops.pad_gemm_program).
The per-tree vectors B, D and V travel as ``(T, 1, I|L)`` so each grid step's
block ``(1, 1, I|L)`` keeps its last two dimensions TPU-tileable. Callers
size ``block_n`` with :func:`tree_gemm_block_n`, which budgets the step's
VMEM (:func:`tree_gemm_vmem_bytes`) under ``repro.kernels.VMEM_BUDGET_BYTES``.

Each contraction runs in the fewest bf16 MXU passes that keep it exact in
float32, which holds only while the program keeps its contract
(``ops.pad_gemm_program`` checks it):

* ``A`` is 0/1 with at most one 1 per column, so each ``S[n, i]`` is a
  single feature ``x[n, f]``. The wrapper splits ``x`` once, outside the
  tree loop, into three bf16 pieces by truncation (:func:`split_bf16`);
  their sum in any order is ``x`` bit for bit, so three one-pass dots
  summed in f32 return the exact float32 feature for the ``S <= B``
  compare.
* ``C`` is in {-1, 0, 1} and the decisions are 0/1, so ``P = D·C`` sums
  small integers: exact in one bf16 pass accumulated in f32.

``A`` and ``C`` travel as bf16 (exact); ``B``, ``D`` and ``V`` stay f32, and
the leaf values are summed in f32 in tree order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES


def split_bf16(x: jnp.ndarray) -> jnp.ndarray:
    """f32 ``x`` as three bf16 pieces, stacked ``(3, *x.shape)``, whose sum
    in any order is ``x`` bit for bit, -0 included. Each piece truncates what
    the ones before it left to its top 16 bits, so it keeps the remainder's
    sign and no sum of pieces rounds. Exact for finite ``|x| >= 2**-102``
    and zero; below that the lowest piece is subnormal, which the TPU
    flushes to zero."""

    def trunc(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)

    x = x.astype(jnp.float32)
    hi = trunc(x)
    rest = jnp.copysign(x - hi, x)
    mid = trunc(rest)
    lo = jnp.copysign(rest - mid, rest)
    return jnp.stack([hi, mid, lo]).astype(jnp.bfloat16)


def tree_gemm_vmem_bytes(block_n: int, F: int, I: int, L: int) -> int:
    """VMEM one grid step holds: double-buffered input/output blocks (the
    ``(1, I|L)`` rows and the ``(block_n, 1)`` output occupy whole (8, 128)
    tiles) plus the temporaries: the three pieces' f32 S and their sum, the
    bf16 decisions, and the f32 P, match and match·V."""
    blocks = (
        2 * (3 * block_n * F + F * I + I * L)  # bf16 x pieces, A, C
        + 4 * (8 * I + 2 * 8 * L)  # f32 B, D, V
        + 4 * block_n * 128  # f32 output column
    )
    temps = block_n * (4 * 4 * I + 2 * I + 4 * 3 * L)
    return 2 * blocks + temps


def tree_gemm_block_n(n_rows: int, F: int, I: int, L: int) -> int:
    """Largest power-of-two row block in [8, 512] whose step fits the VMEM
    budget, and no larger than the row count's own power-of-two bucket (so a
    small batch is not padded up to a full block)."""
    cap = 1 << max(3, (max(n_rows, 1) - 1).bit_length())
    bn = 512
    while bn > 8 and (bn > cap or tree_gemm_vmem_bytes(bn, F, I, L) > VMEM_BUDGET_BYTES):
        bn //= 2
    return bn


def _dot(a, b):
    """One bf16 MXU pass, accumulated in f32."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _kernel(x_ref, a_ref, b_ref, c_ref, d_ref, v_ref, o_ref, *, base: float):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, base)

    a = a_ref[0]
    s = (_dot(x_ref[0], a) + _dot(x_ref[1], a)) + _dot(x_ref[2], a)  # (BN, I): x exactly
    dec = (s <= b_ref[0]).astype(jnp.bfloat16)  # (BN, I) vs (1, I)
    p = _dot(dec, c_ref[0])  # (BN, L): exact small integers
    match = (p == d_ref[0]).astype(jnp.float32)  # (BN, L); ≤ one leaf per row
    o_ref[...] += jnp.sum(match * v_ref[0], axis=1, keepdims=True)


def tree_gemm(
    x: jnp.ndarray,
    A: jnp.ndarray,
    B: jnp.ndarray,
    C: jnp.ndarray,
    D: jnp.ndarray,
    V: jnp.ndarray,
    base: float,
    *,
    block_n: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """x:(N,F) f32 (N % block_n == 0); A:(T,F,I); B:(T,I); C:(T,I,L);
    D:(T,L); V:(T,L). Returns (N,) raw ensemble scores. ``x`` is split into
    its bf16 pieces here, once, so every tree step of a row block reuses
    them."""
    N, F = x.shape
    T, _, I = A.shape
    L = C.shape[2]
    assert N % block_n == 0, (N, block_n)
    grid = (N // block_n, T)
    out = pl.pallas_call(
        functools.partial(_kernel, base=float(base)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((3, block_n, F), lambda n, t: (0, n, 0)),
            pl.BlockSpec((1, F, I), lambda n, t: (t, 0, 0)),
            pl.BlockSpec((1, 1, I), lambda n, t: (t, 0, 0)),
            pl.BlockSpec((1, I, L), lambda n, t: (t, 0, 0)),
            pl.BlockSpec((1, 1, L), lambda n, t: (t, 0, 0)),
            pl.BlockSpec((1, 1, L), lambda n, t: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda n, t: (n, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="tree_gemm",
    )(
        split_bf16(x),
        A.astype(jnp.bfloat16),
        B.astype(jnp.float32).reshape(T, 1, I),
        C.astype(jnp.bfloat16),
        D.astype(jnp.float32).reshape(T, 1, L),
        V.astype(jnp.float32).reshape(T, 1, L),
    )
    return out[:, 0]
