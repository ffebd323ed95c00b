"""Jit'd wrappers around the Pallas kernels.

Dispatch policy: on TPU backends the Pallas kernel runs natively; on CPU the
pure-jnp oracle from :mod:`repro.kernels.ref` runs instead (fused by XLA),
and ``interpret=True`` forces the Pallas kernel body through the interpreter
for correctness tests. All wrappers handle padding so callers pass natural
shapes; padding is constructed to be provably inert (see each pad helper).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as _ref
from repro.kernels.tree_gemm import (
    tree_gemm as _tree_gemm_kernel,
    tree_gemm_block_n,
)
from repro.kernels.featurize import featurize as _featurize_kernel
from repro.kernels.relational import (
    gather_join as _gather_join_kernel,
    segment_agg as _segment_agg_kernel,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def kernels_enabled() -> bool:
    """``RAVEN_KERNELS`` knob: ``off``/``0`` routes relational stages through
    the legacy jnp composition (argsort/searchsorted/segment_sum inline in
    the stage fn) instead of the kernel ops. Anything else (the default)
    uses :func:`gather_join_op`/:func:`segment_agg_op`, which dispatch to
    the Pallas kernels on TPU and the jnp oracles on CPU."""
    return os.environ.get("RAVEN_KERNELS", "on").lower() not in ("off", "0")


def kernel_mode_token() -> str:
    """Content token for the relational-kernel codegen mode. Folded into the
    fingerprints of stages (and plans) containing Join/Aggregate ops so the
    two ``RAVEN_KERNELS`` modes never alias each other's compiled artifacts.
    ``rk1`` versions the relational-kernel emission itself."""
    return "rk1-on" if kernels_enabled() else "rk1-off"


# ---------------------------------------------------------------------------
# tree_gemm
# ---------------------------------------------------------------------------


def pad_gemm_program(A, B, C, D, V, align: int = 128):
    """MXU-align F/I/L. Inert padding proof:
      * extra F rows of A are zero → S unchanged (x is zero-padded to match);
      * extra I columns: threshold +inf ⇒ decision 1, but their C rows are
        zero ⇒ P unchanged;
      * extra L columns: Dcount = -1 can never equal a non-negative path
        count ⇒ match 0 ⇒ V never read (and V is 0 there anyway).

    Raises ``ValueError`` unless A is 0/1 with at most one 1 per column and C
    is in {-1, 0, 1}: the kernel's one-pass contractions are exact only then
    (``repro.kernels.tree_gemm``)."""
    A, C = np.asarray(A), np.asarray(C)
    if not (np.isin(A, (0, 1)).all() and (A.sum(axis=1) <= 1).all()):
        raise ValueError("tree_gemm needs A 0/1 with at most one 1 per column")
    if not np.isin(C, (-1, 0, 1)).all():
        raise ValueError("tree_gemm needs C in {-1, 0, 1}")
    T, F, I = A.shape
    L = C.shape[2]
    Fp, Ip, Lp = _round_up(F, align), _round_up(I, align), _round_up(L, align)
    A2 = np.zeros((T, Fp, Ip), np.float32)
    A2[:, :F, :I] = A
    B2 = np.full((T, Ip), np.float32(np.inf))
    B2[:, :I] = B
    C2 = np.zeros((T, Ip, Lp), np.float32)
    C2[:, :I, :L] = C
    D2 = np.full((T, Lp), np.float32(-1.0))
    D2[:, :L] = D
    V2 = np.zeros((T, Lp), np.float32)
    V2[:, :L] = V
    return A2, B2, C2, D2, V2


@functools.partial(jax.jit, static_argnames=("base", "block_n", "use_pallas", "interpret"))
def tree_gemm_op(
    x, A, B, C, D, V, *, base: float, block_n: int | None = None,
    use_pallas: bool | None = None, interpret: bool = False,
):
    """(N,F) rows → (N,) raw scores. Pads N to block_n and F to A's F;
    ``block_n=None`` sizes the row block from the step's VMEM need."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    N, F = x.shape
    Fk = A.shape[1]
    if not (use_pallas or interpret):
        xp = jnp.pad(x, ((0, 0), (0, Fk - F))) if Fk > F else x
        return _ref.tree_gemm_ref(xp, A, B, C, D, V, base)
    if block_n is None:
        block_n = tree_gemm_block_n(N, Fk, A.shape[2], C.shape[2])
    Np = _round_up(max(N, 1), block_n)
    xp = jnp.pad(x.astype(jnp.float32), ((0, Np - N), (0, Fk - F)))
    out = _tree_gemm_kernel(
        xp, A, B, C, D, V, base, block_n=block_n, interpret=interpret
    )
    return out[:N]


# ---------------------------------------------------------------------------
# featurize
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("cat_segments", "block_n", "use_pallas", "interpret"),
)
def featurize_op(
    num, cat, offset, scale, cat_values, cat_segments,
    *, block_n: int = 256, use_pallas: bool | None = None, interpret: bool = False,
):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not (use_pallas or interpret):
        return _ref.featurize_ref(num, cat, offset, scale, cat_values, cat_segments)
    # row padding/cropping (and zero-width operand widening) live in the
    # kernel wrapper itself — natural shapes in, natural shapes out
    return _featurize_kernel(
        num, cat, offset, scale, cat_values, cat_segments,
        block_n=block_n, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# relational: gather-join and masked segmented aggregate
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("block_n", "use_pallas", "interpret")
)
def gather_join_op(
    fk, skeys, spay, *, block_n: int | None = None,
    use_pallas: bool | None = None, interpret: bool = False,
):
    """Dim-table equi-join gather. fk:(N,) int32; skeys:(M,) sorted *unique*
    int32 dim keys; spay:(M,P) f32 payload aligned to skeys. Returns
    ``(out, hit)``: out:(N,P) f32 (zero on miss), hit:(N,) bool. Miss rows
    zero their payload in every dispatch path, so kernel and oracle agree
    bitwise on all rows."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not (use_pallas or interpret):
        return _ref.gather_join_ref(fk, skeys, spay)
    return _gather_join_kernel(
        fk, skeys, spay, block_n=block_n, interpret=interpret
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "block_n", "use_pallas", "interpret"),
)
def segment_agg_op(
    vals, w, sid, *, num_segments: int, block_n: int | None = None,
    use_pallas: bool | None = None, interpret: bool = False,
):
    """Masked segmented aggregate. vals:(N,C) f32; w:(N,) f32 validity
    weights (the fused filter mask); sid:(N,) int32 in [0, num_segments).
    ``block_n=None`` sizes the row block from the step's VMEM need.
    Returns ``(counts, sums, mins, maxs)`` — counts:(S,), the rest (S,C);
    mins/maxs are +inf/-inf where a segment has no valid rows (callers
    replace empties via ``counts > 0``)."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not (use_pallas or interpret):
        return _ref.segment_agg_ref(vals, w, sid, num_segments=num_segments)
    return _segment_agg_kernel(
        vals, w, sid, num_segments=num_segments,
        block_n=block_n, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# attention (wrappers defined with the kernels in flash_attention.py /
# decode_attention.py; re-exported here for a single import surface)
# ---------------------------------------------------------------------------

from repro.kernels.flash_attention import flash_attention_op  # noqa: E402
from repro.kernels.decode_attention import decode_attention_op  # noqa: E402
