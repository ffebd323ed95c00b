"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

Interpret mode runs a kernel body on the CPU and accepts block shapes and
VMEM footprints that Mosaic, the TPU kernel compiler, refuses. These tests
compile each kernel the served path places — ``tree_gemm``, ``featurize``,
``gather_join``, ``segment_agg`` — for one chip of a *described* ``v5e:2x2``
topology (the TPU compiler runs here; no chip is attached) and require the
compiled program to hold the kernel (``tpu_custom_call``). Widths:

  * flights as the chip smoke serves it (4 numerics and 33 categoricals, a
    20-tree depth-3 ensemble), pruned by the optimizer and at its full
    ~6.5k one-hot width;
  * hospital (9 numerics, 50 one-hot columns) and fig. 12's largest model,
    500 trees at depth 8; hospital's GB 300x6 at the bulk cell's 65,536 rows
    and at an 8-row bucket, below bf16's 16-row tile;
  * a 16,384-row dimension table for the gather-join, and the largest one
    its VMEM budget admits; the segmented aggregate at 1 and 64 segments and
    at the most its VMEM budget admits.

The topology is described inside a module fixture, never at import: only the
worker running this file loads the TPU library. JAX's persistent compilation
cache is off around the compiles (an executable compiled for a described
chip cannot be read back without one).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.relational import (
    gather_join_block_n,
    segment_agg_max_segments,
)

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    """Compile for the described chip; returns the compiled program text."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _segments(cards):
    out, start = [], 0
    for c in cards:
        out.append((start, c))
        start += c
    return tuple(out), start


def _flights_cards():
    import numpy as np

    from repro.data.datasets import _split_cards

    # make_flights draws its category domains first from its seeded rng
    return _split_cards(6471, 33, np.random.default_rng(0))


# (rows, trees, padded features, padded internal nodes, padded leaves)
TREE_GEMM_WIDTHS = {
    # flights GB 20x3 after the optimizer prunes unused one-hot columns
    "flights-served": (4096, 20, 128, 128, 128),
    # flights GB 20x3 over every one of its 6475 features
    "flights-full": (4096, 20, 6528, 128, 128),
    # fig. 12: hospital (59 features), 500 trees at depth 8
    "fig12-500x8": (4096, 500, 128, 256, 256),
    # the bulk cell's requests: hospital GB 300x6 at 65,536 rows
    "hospital-bulk": (65_536, 300, 128, 128, 128),
    # a bucket under bf16's 16-row tile, so the x pieces' block is 8 rows
    "hospital-8-rows": (8, 300, 128, 128, 128),
}


@pytest.mark.parametrize("width", sorted(TREE_GEMM_WIDTHS))
def test_tree_gemm_compiles(one_chip, width):
    N, T, F, I, L = TREE_GEMM_WIDTHS[width]
    s = lambda *shape: _spec(one_chip, shape)  # noqa: E731
    _compile(
        lambda x, A, B, C, D, V: ops.tree_gemm_op(
            x, A, B, C, D, V, base=0.0, use_pallas=True
        ),
        s(N, F), s(T, F, I), s(T, I), s(T, I, L), s(T, L), s(T, L),
    )


@pytest.mark.parametrize("dataset", ["hospital", "flights"])
def test_featurize_compiles(one_chip, dataset):
    if dataset == "hospital":
        n_num, cards = 9, [2] * 9 + [3, 3, 4, 6, 7, 9]
    else:
        n_num, cards = 4, _flights_cards()
    segments, n_values = _segments(cards)
    N = 4096
    _compile(
        lambda num, cat, off, sc, vals: ops.featurize_op(
            num, cat, off, sc, vals, segments, use_pallas=True
        ),
        _spec(one_chip, (N, n_num)),
        _spec(one_chip, (N, len(cards)), jnp.int32),
        _spec(one_chip, (n_num,)),
        _spec(one_chip, (n_num,)),
        _spec(one_chip, (n_values,), jnp.int32),
    )


def _largest_admitted_dim(P: int) -> int:
    """The largest multiple of 128 dimension rows the gather-join admits."""
    m = 128
    while gather_join_block_n(m + 128, P) is not None:
        m += 128
    return m


@pytest.mark.parametrize(
    "dim_rows,payload,fact_rows",
    [(16_384, 2, 16_384), ("max", 128, 4096)],
)
def test_gather_join_compiles(one_chip, dim_rows, payload, fact_rows):
    M = _largest_admitted_dim(payload) if dim_rows == "max" else dim_rows
    assert gather_join_block_n(M, payload) is not None
    _compile(
        lambda fk, keys, pay: ops.gather_join_op(fk, keys, pay, use_pallas=True),
        _spec(one_chip, (fact_rows,), jnp.int32),
        _spec(one_chip, (M,), jnp.int32),
        _spec(one_chip, (M, payload)),
    )


@pytest.mark.parametrize("segments", [1, 64, "max"])
def test_segment_agg_compiles(one_chip, segments):
    N, C = 16_384, 5
    if segments == "max":
        segments = segment_agg_max_segments(C)
    _compile(
        lambda v, w, sid: ops.segment_agg_op(
            v, w, sid, num_segments=segments, use_pallas=True
        ),
        _spec(one_chip, (N, C)),
        _spec(one_chip, (N,)),
        _spec(one_chip, (N,), jnp.int32),
    )
