"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.tensor.tree2tensor import build_gemm_program, gemm_predict


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("shape", [(2, 128, 4, 64), (1, 256, 8, 32), (3, 64, 6, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(shape, dtype, causal):
    B, S, H, D = shape
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(k1, shape, dtype)
    k = _rand(k2, shape, dtype)
    v = _rand(k3, shape, dtype)
    got = ops.flash_attention_op(q, k, v, causal=causal, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


@pytest.mark.parametrize("kv_heads", [1, 2, 4])
def test_flash_attention_gqa(kv_heads):
    B, S, H, D = 2, 128, 4, 64
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(k1, (B, S, H, D), jnp.float32)
    k = _rand(k2, (B, S, kv_heads, D), jnp.float32)
    v = _rand(k3, (B, S, kv_heads, D), jnp.float32)
    got = ops.flash_attention_op(q, k, v, causal=True, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("shape", [(2, 128, 4, 64), (4, 512, 2, 64), (1, 64, 8, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(shape, dtype):
    B, S, KH, D = shape
    H = KH * 2
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(k1, (B, H, D), dtype)
    kc = _rand(k2, (B, S, KH, D), dtype)
    vc = _rand(k3, (B, S, KH, D), dtype)
    lengths = jnp.asarray(
        np.random.default_rng(0).integers(1, S, size=B), jnp.int32
    )
    got = ops.decode_attention_op(q, kc, vc, lengths, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, lengths)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


@pytest.mark.parametrize("n_estimators,max_depth", [(1, 3), (8, 4), (20, 2)])
def test_tree_gemm_kernel_sweep(hospital, n_estimators, max_depth):
    from repro.ml import GradientBoostingClassifier

    ds = hospital
    joined = ds.joined_columns()
    X = np.stack([joined[c] for c in ds.numeric], 1)
    gb = GradientBoostingClassifier(
        n_estimators=n_estimators, max_depth=max_depth
    ).fit(X, ds.label)
    prog = build_gemm_program(gb.ensemble)
    Xj = jnp.asarray(X[:512], jnp.float32)
    want = gemm_predict(prog, Xj)
    A, B, C, D, V = ops.pad_gemm_program(
        prog.A, prog.B, prog.C, prog.Dcount, prog.V
    )
    got = ops.tree_gemm_op(
        Xj, jnp.asarray(A), jnp.asarray(B), jnp.asarray(C), jnp.asarray(D),
        jnp.asarray(V), base=prog.base, interpret=True,
    )
    if n_estimators == 1:  # one leaf value plus base: nothing to reorder
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.kernel_parity
@pytest.mark.parametrize("N", [0, 100, 256, 257])
@pytest.mark.parametrize(
    "n_num,segs",
    [
        (5, (4, 4, 4)),
        (1, (2,)),
        (9, (3, 7, 2, 5)),
        (4, ()),   # numeric-only: no one-hot segments
        (0, (3, 5)),  # categorical-only: no scaler columns
    ],
)
def test_featurize_kernel_sweep(n_num, segs, N):
    """Fused featurize kernel vs the jnp oracle — including row counts that
    are not a multiple of ``block_n`` (internal pad/crop) and zero-width
    numeric/categorical operands."""
    rng = np.random.default_rng(3)
    num = jnp.asarray(rng.normal(size=(N, n_num)), jnp.float32)
    cat = jnp.asarray(
        np.stack([rng.integers(0, s, N) for s in segs], 1)
        if segs else np.zeros((N, 0)),
        jnp.int32,
    )
    offset = jnp.asarray(rng.normal(size=n_num), jnp.float32)
    scale = jnp.asarray(rng.uniform(0.5, 2.0, size=n_num), jnp.float32)
    starts = np.cumsum([0] + list(segs))[:-1]
    cat_values = jnp.asarray(
        np.concatenate([np.arange(s) for s in segs] or [np.zeros(0)]),
        jnp.int32,
    )
    cat_segments = tuple(
        (int(s), int(l)) for s, l in zip(starts, segs)
    )
    got = ops.featurize_op(
        num, cat, offset, scale, cat_values, cat_segments, interpret=True
    )
    want = ref.featurize_ref(num, cat, offset, scale, cat_values, cat_segments)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    assert got.shape == (N, n_num + sum(segs))


@pytest.mark.kernel_parity
def test_featurize_kernel_bitwise_vs_host_featurization():
    """The fused kernel is *bitwise* identical to the host numpy
    featurization path for scaler + one-hot columns (both are elementwise
    f32); this is what lets split plans keep host-path semantics."""
    rng = np.random.default_rng(7)
    N, n_num, segs = 300, 6, (4, 9)
    num_np = rng.normal(size=(N, n_num)).astype(np.float32)
    cat_np = np.stack([rng.integers(0, s, N) for s in segs], 1).astype(np.int32)
    offset = rng.normal(size=n_num).astype(np.float32)
    scale = rng.uniform(0.5, 2.0, size=n_num).astype(np.float32)
    starts = np.cumsum([0] + list(segs))[:-1]
    cat_values = np.concatenate([np.arange(s) for s in segs]).astype(np.int32)
    cat_segments = tuple((int(s), int(l)) for s, l in zip(starts, segs))

    got = np.asarray(
        ops.featurize_op(
            jnp.asarray(num_np), jnp.asarray(cat_np), jnp.asarray(offset),
            jnp.asarray(scale), jnp.asarray(cat_values), cat_segments,
            interpret=True,
        )
    )
    scaled = (num_np - offset[None, :]) * scale[None, :]
    onehots = [
        (cat_np[:, j : j + 1] == cat_values[s : s + l][None, :]).astype(
            np.float32
        )
        for j, (s, l) in enumerate(cat_segments)
    ]
    want = np.concatenate([scaled, *onehots], axis=1)
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))


def test_tree_gemm_padding_is_inert(hospital):
    """MXU padding must not change scores (the pad proof in ops.py)."""
    from repro.ml import DecisionTreeClassifier

    ds = hospital
    joined = ds.joined_columns()
    X = np.stack([joined[c] for c in ds.numeric], 1)
    dt = DecisionTreeClassifier(max_depth=5).fit(X, ds.label)
    prog = build_gemm_program(dt.ensemble)
    Xj = jnp.asarray(X[:128], jnp.float32)
    want = gemm_predict(prog, Xj)
    for align in (8, 64, 128, 256):
        A, B, C, D, V = ops.pad_gemm_program(
            prog.A, prog.B, prog.C, prog.Dcount, prog.V, align=align
        )
        got = ops.tree_gemm_op(
            Xj, jnp.asarray(A), jnp.asarray(B), jnp.asarray(C),
            jnp.asarray(D), jnp.asarray(V), base=prog.base, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _truncate(v):
    """``v`` with its float32 bit pattern cut to the top 16 bits."""
    return (v.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)


def test_split_bf16_pieces_are_exact_and_sum_to_x_bitwise():
    """The kernel's split of ``x``: the bf16 pieces are the truncation
    pieces computed here in float32 (so casting them lost nothing), and both
    summation orders give ``x`` back bit for bit."""
    from repro.kernels.tree_gemm import split_bf16

    f32 = np.float32
    big, tiny = np.finfo(f32).max, np.finfo(f32).tiny
    edges = np.array([
        0.0, -0.0, big, -big, tiny * 2**24, -tiny * 2**24,
        np.nextafter(f32(2.0), f32(0)), np.nextafter(f32(2.0**-101), f32(0)),
        np.nextafter(f32(1), f32(0)), np.nextafter(f32(1), f32(2)), -1.0,
    ], f32)
    rng = np.random.default_rng(18)
    normals = rng.standard_normal(1 << 20).astype(f32)
    # bit patterns over every exponent the split covers: 2**-102 to FLT_MAX
    bits = rng.integers(0, 1 << 32, 1 << 20, dtype=np.uint64).astype(np.uint32)
    exponent = (bits >> 23) & 0xFF
    patterns = bits[(exponent >= 25) & (exponent < 255)].view(f32)
    x = np.concatenate([edges, normals, patterns])

    pieces = np.asarray(jax.jit(split_bf16)(jnp.asarray(x)))
    assert pieces.dtype == jnp.bfloat16 and pieces.shape == (3, len(x))
    hi, mid, lo = pieces.astype(f32)
    want_hi = _truncate(x)
    want_rest = np.copysign(x - want_hi, x)
    want_mid = _truncate(want_rest)
    want_lo = np.copysign(want_rest - want_mid, want_rest)
    for got, want in ((hi, want_hi), (mid, want_mid), (lo, want_lo)):
        np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    np.testing.assert_array_equal(((hi + mid) + lo).view(np.uint32), x.view(np.uint32))
    np.testing.assert_array_equal(((hi + lo) + mid).view(np.uint32), x.view(np.uint32))


def _full_tree_ensemble(rng, n_trees, depth, n_features):
    """Full trees whose thresholds need all 24 bits of their mantissa."""
    from repro.ml.trees import LEAF, TreeEnsemble

    n_int, per = 2**depth - 1, 2**(depth + 1) - 1
    k = np.arange(per)
    base = (np.arange(n_trees) * per)[:, None]
    feature = np.where(k < n_int, rng.integers(0, n_features, (n_trees, per)), LEAF)
    threshold = rng.standard_normal((n_trees, per)).astype(np.float32)
    threshold = (threshold.view(np.uint32) | np.uint32(1)).view(np.float32)
    leaf = rng.standard_normal((n_trees, per)).astype(np.float32)
    return TreeEnsemble(
        feature=feature.reshape(-1), threshold=threshold.astype(np.float64).reshape(-1),
        left=(base + np.minimum(2 * k + 1, per - 1)).reshape(-1),
        right=(base + np.minimum(2 * k + 2, per - 1)).reshape(-1),
        leaf_value=np.where(k < n_int, 0.0, leaf).reshape(-1),
        tree_offsets=np.arange(n_trees + 1) * per, tree_weight=np.ones(n_trees),
        base_score=0.375, post_transform="none", n_features=n_features,
    )


def _walk(ens, X):
    """float32 scores, trees added in the kernel's order: base, tree 0, 1, …"""
    out = np.full(len(X), np.float32(ens.base_score))
    for r, row in enumerate(X):
        for sl in ens.tree_slices():
            node = sl.start
            while ens.feature[node] >= 0:
                go_left = row[ens.feature[node]] <= np.float32(ens.threshold[node])
                node = ens.left[node] if go_left else ens.right[node]
            out[r] = np.float32(out[r] + np.float32(ens.leaf_value[node]))
    return out


def test_tree_gemm_decides_on_the_exact_float32_feature():
    """Rows at each threshold and one ulp either side of it: the kernel's
    scores equal a float32 walk bit for bit, while features rounded to 16
    bits (as ``Precision.HIGH`` does) route some of the same rows elsewhere."""
    rng = np.random.default_rng(5)
    ens = _full_tree_ensemble(rng, n_trees=4, depth=3, n_features=6)
    split = np.flatnonzero(ens.feature >= 0)
    X = np.repeat(rng.standard_normal((1, ens.n_features)).astype(np.float32),
                  3 * len(split), axis=0)
    for j, node in enumerate(split):
        b = np.float32(ens.threshold[node])
        X[3 * j:3 * j + 3, ens.feature[node]] = [
            np.nextafter(b, np.float32(-np.inf)), b, np.nextafter(b, np.float32(np.inf))]
    prog = build_gemm_program(ens)
    A, B, C, D, V = ops.pad_gemm_program(prog.A, prog.B, prog.C, prog.Dcount, prog.V)
    got = np.asarray(ops.tree_gemm_op(
        jnp.asarray(X), *map(jnp.asarray, (A, B, C, D, V)), base=prog.base,
        interpret=True,
    ))
    want = _walk(ens, X)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))

    hi = X.astype(jnp.bfloat16).astype(np.float32)
    rounded = hi + (X - hi).astype(jnp.bfloat16).astype(np.float32)
    assert (_walk(ens, rounded) != want).any()


@pytest.mark.parametrize("fault", ["two ones in a column of A", "A not 0/1",
                                   "C outside {-1, 0, 1}"])
def test_pad_gemm_program_refuses_what_the_kernel_cannot_run_exactly(fault):
    T, F, I, L = 2, 4, 3, 4
    A = np.zeros((T, F, I), np.float32)
    A[:, 0, :] = 1
    C = np.zeros((T, I, L), np.float32)
    C[:, 0, :2], C[:, 0, 2:] = 1, -1
    if fault == "two ones in a column of A":
        A[1, 2, 1] = 1
    elif fault == "A not 0/1":
        A[0, 0, 2] = 0.5
    else:
        C[1, 2, 3] = 2
    B, D, V = np.zeros((T, I)), np.zeros((T, L)), np.zeros((T, L))
    with pytest.raises(ValueError, match="tree_gemm needs"):
        ops.pad_gemm_program(A, B, C, D, V)


@pytest.mark.parametrize(
    "N,F,I,L",
    [(100, 128, 128, 128), (4096, 128, 128, 128), (4096, 6528, 128, 128),
     (4096, 128, 256, 256)],
)
def test_tree_gemm_block_n_fits_vmem_and_the_batch(N, F, I, L):
    from repro.kernels import VMEM_BUDGET_BYTES
    from repro.kernels.tree_gemm import tree_gemm_block_n, tree_gemm_vmem_bytes

    bn = tree_gemm_block_n(N, F, I, L)
    assert bn & (bn - 1) == 0 and 8 <= bn <= 512
    assert bn <= max(8, 1 << (N - 1).bit_length())  # no padding past the bucket
    assert tree_gemm_vmem_bytes(bn, F, I, L) <= VMEM_BUDGET_BYTES
    if F > 128:
        # x travels as three double-buffered bf16 pieces: 1.5x one f32 block
        grow = tree_gemm_vmem_bytes(bn, F, I, L) - tree_gemm_vmem_bytes(bn, 128, I, L)
        assert grow >= 2 * 3 * 2 * bn * (F - 128) > 2 * 4 * bn * (F - 128)


def test_tree_gemm_runtime_follows_use_pallas(monkeypatch):
    """``use_pallas=None`` means the tree_gemm kernel on TPU (as for the
    other kernels) and the XLA einsum elsewhere; EXPLAIN says which."""
    import repro.tensor.compile as tc

    assert tc.tree_runtime("gemm", None) == "gemm (XLA einsum)"
    assert tc.tree_runtime("gemm", True) == "gemm (tree_gemm kernel)"
    assert tc.tree_runtime("traversal", True) == "traversal (XLA gathers)"
    monkeypatch.setattr(tc, "_on_tpu", lambda: True)
    assert tc.tree_runtime("gemm", None) == "gemm (tree_gemm kernel)"
    assert tc.tree_runtime("gemm", False) == "gemm (XLA einsum)"
