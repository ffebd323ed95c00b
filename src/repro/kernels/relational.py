"""Pallas TPU kernels for the relational half of the runtime: dim-table
gather-join and masked segmented group-by aggregation.

The paper's thesis is that relational and ML operators share one IR so each
side can run on the best runtime; these kernels are what lets Join and
Filter→Aggregate chains stay *inside* a fused pure stage instead of standing
alone as generic jnp ops around a host boundary.

Join strategy (dim-table equi-join, unique keys): instead of
argsort + searchsorted + gather, each row block builds a one-hot match matrix
against the (VMEM-resident) dim-key vector and gathers the payload with one
MXU matmul — ``out = onehot @ payload``. With unique dim keys each one-hot
row has at most a single 1.0, so the matmul reproduces the gathered payload
value *bitwise* (x * 1.0 accumulated with zeros is exact in f32); miss rows
produce all-zero payload and ``hit=0``, matching :func:`gather_join_ref`.
The upstream filter's validity mask is fused downstream (``valid & hit``) —
the kernel itself never materializes filtered rows.

Aggregate strategy: one grid pass over row blocks accumulating into a
(segments × columns) block that stays resident across grid steps
(``@pl.when(program_id == 0)`` init, then ``+=``). Sums and counts are one
one-hot matmul per block (`onehot.T @ (vals * w)` with the weight column
stacked in), min/max are masked broadcast reductions. The filter mask ``w``
is folded in as the weight — filtered rows contribute exactly zero and are
never materialized.

Both kernels use the same treatment as the ``featurize`` kernel: rows
padded to a multiple of ``block_n`` with provably inert values and cropped
back, zero-width operands widened to one inert column, ``interpret=True``
for CPU correctness tests. Their matmuls run at ``Precision.HIGHEST`` (the
TPU's default f32 matmul rounds operands to bf16, which would break the
bitwise gather and the f32 sums).

The gather-join keeps the whole padded payload resident in VMEM, so it can
only hold dimension tables up to a size: :func:`gather_join_block_n` picks
the row block from the step's VMEM need and returns ``None`` when no block
fits, and the Join lowering then keeps the jnp gather (see
``repro.tensor.compile.join_kernel_choice``). The segmented aggregate keeps
its ``(segments × columns)`` accumulators resident the same way:
:func:`segment_agg_block_n` bounds the segment count, and the Aggregate
lowering keeps the jnp segment ops beyond it
(``repro.tensor.compile.aggregate_kernel_choice``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES

_HI = jax.lax.Precision.HIGHEST


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# gather-join
# ---------------------------------------------------------------------------


def _gather_join_body(fk_ref, keys_ref, pay_ref, out_ref, hit_ref, *, m_real):
    fk = fk_ref[...]  # (BN, 1) int32
    keys = keys_ref[...]  # (1, Mp) int32
    onehot = fk == keys  # (BN, Mp)
    # padded key columns must never match, whatever their pad value is
    col = jax.lax.broadcasted_iota(jnp.int32, onehot.shape, 1)
    onehot_f = jnp.where(onehot & (col < m_real), 1.0, 0.0).astype(jnp.float32)
    out_ref[...] = jnp.dot(
        onehot_f, pay_ref[...], precision=_HI,
        preferred_element_type=jnp.float32,
    )
    hit_ref[...] = jnp.sum(onehot_f, axis=1, keepdims=True)


def gather_join_vmem_bytes(block_n: int, M: int, P: int) -> int:
    """VMEM one gather-join step holds: double-buffered blocks (the resident
    ``(Mp, Pp)`` payload and ``(1, Mp)`` keys, the row blocks; width-1
    columns occupy whole 128-lane tiles) plus the ``(block_n, Mp)`` match,
    column-index and one-hot temporaries."""
    Mp = _round_up(max(M, 1), 128)
    Pp = _round_up(max(P, 1), 128)
    blocks = Mp * Pp + 8 * Mp + block_n * (Pp + 2 * 128)
    return 4 * (2 * blocks + 3 * block_n * Mp)


def gather_join_block_n(M: int, P: int):
    """Largest power-of-two row block in [8, 256] whose step fits the VMEM
    budget for an ``M``-row, ``P``-column dimension payload, or ``None``
    when even an 8-row block does not: the kernel cannot hold that
    dimension table."""
    bn = 256
    while bn >= 8:
        if gather_join_vmem_bytes(bn, M, P) <= VMEM_BUDGET_BYTES:
            return bn
        bn //= 2
    return None


def gather_join(
    fk: jnp.ndarray,
    skeys: jnp.ndarray,
    spay: jnp.ndarray,
    *,
    block_n: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """fk:(N,) int32 fact keys; skeys:(M,) int32 *unique* dim keys;
    spay:(M,P) f32 payload aligned to ``skeys``. Returns ``(out, hit)``:
    out:(N,P) f32 gathered payload (zero on miss), hit:(N,) bool.
    ``block_n=None`` sizes the row block with :func:`gather_join_block_n`.

    Inert padding proof: extra rows only extend the grid and are cropped;
    extra key columns are masked by the in-kernel ``col < M`` guard (their
    payload rows are zero anyway); extra payload columns are zero and
    cropped.
    """
    N = fk.shape[0]
    M, P = spay.shape
    if block_n is None:
        block_n = gather_join_block_n(M, P)
        if block_n is None:
            raise ValueError(
                f"gather_join cannot hold a {M}x{P} dimension payload in VMEM"
            )
    Mp = _round_up(max(M, 1), 128)
    Pp = _round_up(max(P, 1), 128)
    Np = _round_up(max(N, 1), block_n)
    fk = jnp.pad(fk.astype(jnp.int32), (0, Np - N))
    keys = jnp.pad(skeys.astype(jnp.int32), (0, Mp - M))
    pay = jnp.pad(spay.astype(jnp.float32), ((0, Mp - M), (0, Pp - P)))
    out, hit = pl.pallas_call(
        functools.partial(_gather_join_body, m_real=M),
        grid=(Np // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda n: (n, 0)),
            pl.BlockSpec((1, Mp), lambda n: (0, 0)),
            pl.BlockSpec((Mp, Pp), lambda n: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, Pp), lambda n: (n, 0)),
            pl.BlockSpec((block_n, 1), lambda n: (n, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, Pp), jnp.float32),
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="gather_join",
    )(fk.reshape(-1, 1), keys.reshape(1, -1), pay)
    return out[:N, :P], hit[:N, 0] > 0


# ---------------------------------------------------------------------------
# masked segmented aggregate
# ---------------------------------------------------------------------------


def _segment_agg_body(
    vals_ref, w_ref, sid_ref, sum_ref, min_ref, max_ref, *, n_cols
):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        min_ref[...] = jnp.full_like(min_ref, jnp.inf)
        max_ref[...] = jnp.full_like(max_ref, -jnp.inf)

    vals = vals_ref[...]  # (BN, Cp) f32, col 0 is the weight itself
    w = w_ref[...]  # (BN, 1) f32 validity weights
    sid = sid_ref[...]  # (BN, 1) int32
    seg = jax.lax.broadcasted_iota(jnp.int32, (vals.shape[0], sum_ref.shape[0]), 1)
    onehot = sid == seg  # (BN, Sp)
    onehot_f = jnp.where(onehot, 1.0, 0.0).astype(jnp.float32)
    # sums and counts in one MXU pass: contract the row axis
    sum_ref[...] += jax.lax.dot_general(
        onehot_f, vals * w, (((0,), (0,)), ((), ())),
        precision=_HI, preferred_element_type=jnp.float32,
    )
    mask = onehot & (w > 0)  # (BN, Sp): row feeds segment AND survived filter
    for j in range(n_cols):
        colv = vals[:, j : j + 1]  # (BN, 1) static slice
        mn = jnp.min(jnp.where(mask, colv, jnp.inf), axis=0)  # (Sp,)
        mx = jnp.max(jnp.where(mask, colv, -jnp.inf), axis=0)
        min_ref[j : j + 1, :] = jnp.minimum(min_ref[j : j + 1, :], mn[None, :])
        max_ref[j : j + 1, :] = jnp.maximum(max_ref[j : j + 1, :], mx[None, :])


def segment_agg_vmem_bytes(block_n: int, S: int, C: int) -> int:
    """VMEM one segment-agg step holds for ``S`` segments and ``C`` source
    columns: double-buffered row blocks (width-1 columns occupy whole
    128-lane tiles), the resident ``(Sp, Cp)`` sums — double-buffered, plus
    the matmul result and its reload — and ``(C8, Sp)`` extrema, and the
    ``(block_n, Sp)`` one-hot and mask temporaries."""
    Sp = _round_up(max(S, 1), 128)
    Cp = _round_up(C + 1, 128)
    C8 = _round_up(C + 1, 8)
    blocks = block_n * (Cp + 2 * 128)
    return 4 * (2 * blocks + 4 * Sp * Cp + 4 * C8 * Sp + 2 * block_n * Sp)


def segment_agg_block_n(S: int, C: int):
    """Largest power-of-two row block in [8, 256] whose step fits the VMEM
    budget for ``S`` segments over ``C`` source columns, or ``None`` when
    even an 8-row block does not: the kernel cannot hold that many
    segments."""
    bn = 256
    while bn >= 8:
        if segment_agg_vmem_bytes(bn, S, C) <= VMEM_BUDGET_BYTES:
            return bn
        bn //= 2
    return None


def segment_agg_max_segments(C: int) -> int:
    """The largest power-of-two segment count (the serving path's slot
    buckets are powers of two) the kernel holds over ``C`` source
    columns."""
    s = 1
    while segment_agg_block_n(2 * s, C) is not None:
        s *= 2
    return s


def segment_agg(
    vals: jnp.ndarray,
    w: jnp.ndarray,
    sid: jnp.ndarray,
    *,
    num_segments: int,
    block_n: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """vals:(N,C) f32 aggregate source columns; w:(N,) f32 validity weights
    (the fused filter mask); sid:(N,) int32 segment ids in
    ``[0, num_segments)``. Returns ``(counts, sums, mins, maxs)``:
    counts:(S,) weighted row counts; sums:(S,C) masked segment sums;
    mins/maxs:(S,C) masked extrema (+inf/-inf where a segment has no valid
    rows — callers replace empties via ``counts > 0``).
    ``block_n=None`` sizes the row block with :func:`segment_agg_block_n`.

    Inert padding proof: padded rows carry ``w=0, sid=0, vals=0`` — they add
    ``0 * 0`` to segment 0's sums and are excluded from min/max by the
    ``w > 0`` mask; padded segment columns receive no real sid and are
    cropped; padded value columns are cropped.
    """
    N, C = vals.shape
    S = num_segments
    if block_n is None:
        block_n = segment_agg_block_n(S, C)
        if block_n is None:
            raise ValueError(
                f"segment_agg cannot hold {S} segments x {C} columns in VMEM"
            )
    Np = _round_up(max(N, 1), block_n)
    Sp = _round_up(max(S, 1), 128)
    Cp = _round_up(C + 1, 128)  # col 0 = weight (counts ride the same matmul)
    C8 = _round_up(max(C + 1, 1), 8)
    stacked = jnp.concatenate(
        [w.astype(jnp.float32).reshape(-1, 1), vals.astype(jnp.float32)], axis=1
    )
    stacked = jnp.pad(stacked, ((0, Np - N), (0, Cp - (C + 1))))
    wp = jnp.pad(w.astype(jnp.float32), (0, Np - N))
    sidp = jnp.pad(sid.astype(jnp.int32), (0, Np - N))
    sums, mins, maxs = pl.pallas_call(
        functools.partial(_segment_agg_body, n_cols=C + 1),
        grid=(Np // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, Cp), lambda n: (n, 0)),
            pl.BlockSpec((block_n, 1), lambda n: (n, 0)),
            pl.BlockSpec((block_n, 1), lambda n: (n, 0)),
        ],
        out_specs=[
            pl.BlockSpec((Sp, Cp), lambda n: (0, 0)),
            pl.BlockSpec((C8, Sp), lambda n: (0, 0)),
            pl.BlockSpec((C8, Sp), lambda n: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Sp, Cp), jnp.float32),
            jax.ShapeDtypeStruct((C8, Sp), jnp.float32),
            jax.ShapeDtypeStruct((C8, Sp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="segment_agg",
    )(stacked, wp.reshape(-1, 1), sidp.reshape(-1, 1))
    counts = sums[:S, 0]
    return counts, sums[:S, 1 : C + 1], mins[1 : C + 1, :S].T, maxs[1 : C + 1, :S].T
