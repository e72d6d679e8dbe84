"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing here runs on a chip: the TPU compiler compiles for a v5e that is
only described (``jax.experimental.topologies``), so a kernel the chip's
compiler would refuse — a block not aligned to the tiling, more VMEM than
a kernel may use, a shape Mosaic cannot lower — fails here, where the
CPU tests run every kernel in interpret mode and cannot see it. Shapes
are those of the one-chip smoke configuration: 1M x 128 f32 rows,
1024 lists, 32 probes, a 1024-query bucket, k=10.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

N_ROWS, DIM, N_LISTS, N_PROBES, Q, K = 1_000_000, 128, 1024, 32, 1024, 10
# Padded list capacity of a balanced 1M-row build (next pow2 of the
# largest list; the mean list holds ~977 rows).
CAP = 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described device can be written to the persistent
    # cache but never read back without the chip: keep the cache off.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip; return its HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _cells():
    from raft_tpu.neighbors.ivf_flat import _CELL_QROWS

    return (Q * N_PROBES) // _CELL_QROWS + N_LISTS, _CELL_QROWS


def test_describes_a_v5e(one_chip):
    assert next(iter(one_chip.device_set)).device_kind == "TPU v5 lite"


def test_brute_force_fused_knn(one_chip):
    """Brute-force serving: the 1024-query bucket against all 1M rows."""
    from raft_tpu.ops.fused_knn import fused_knn

    hlo = _compile(one_chip, lambda q, x: fused_knn(q, x, K),
                   ((Q, DIM), jnp.float32), ((N_ROWS, DIM), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("bf16", [None, "split"])
def test_kmeans_fused_l2_nn(one_chip, bf16):
    """The IVF build's k-means assignment: every row against 1024
    centers, in the f32 tier and the split-bf16 tier of the EM."""
    from raft_tpu.ops.fused_knn import fused_knn

    hlo = _compile(one_chip,
                   lambda x, c: fused_knn(x, c, 1, bf16=bf16 is not None,
                                          qsplit=bf16 == "split"),
                   ((N_ROWS, DIM), jnp.float32), ((N_LISTS, DIM), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_ivf_flat_cells_and_bucketed_scans(one_chip):
    """IVF-Flat: the packed-cells scan that serves the large buckets and
    the bucket-table scan of fused_batch_knn."""
    from raft_tpu.ops.fused_knn import fused_batch_knn, fused_cells_knn

    max_cells, qrows = _cells()
    hlo = _compile(one_chip,
                   lambda cl, q, x, bad: fused_cells_knn(cl, q, x, bad, K),
                   ((max_cells,), jnp.int32),
                   ((max_cells, qrows, DIM), jnp.float32),
                   ((N_LISTS, CAP, DIM), jnp.float32),
                   ((N_LISTS, CAP), jnp.bool_))
    assert "tpu_custom_call" in hlo
    hlo = _compile(one_chip,
                   lambda q, x, bad: fused_batch_knn(q, x, bad, K),
                   ((N_LISTS, qrows, DIM), jnp.float32),
                   ((N_LISTS, CAP, DIM), jnp.float32),
                   ((N_LISTS, CAP), jnp.bool_))
    assert "tpu_custom_call" in hlo


def test_ivf_flat_cells_scan_over_large_lists(one_chip):
    """The packed-cells scan over lists too large for the default scoped
    VMEM: the list placement of a 4M-row corpus on four chips (one
    shard's 512 list slots padded to 16384 rows), where the kernel asks
    for the VMEM it needs."""
    from raft_tpu.neighbors.ivf_flat import _cells_eligible
    from raft_tpu.ops.fused_knn import fused_cells_knn

    n_slots, cap = 512, 16384
    assert _cells_eligible("bucketed", K, 0, cap, DIM, Q, N_PROBES, n_slots)
    max_cells, qrows = _cells()
    hlo = _compile(one_chip,
                   lambda cl, q, x, bad: fused_cells_knn(cl, q, x, bad, K),
                   ((max_cells,), jnp.int32),
                   ((max_cells, qrows, DIM), jnp.float32),
                   ((n_slots, cap, DIM), jnp.float32),
                   ((n_slots, cap), jnp.bool_))
    assert "tpu_custom_call" in hlo


def test_ivf_pq_compressed_scan(one_chip):
    """IVF-PQ (pq_bits=8, default pq_dim 64): the compressed-domain scan
    over transposed packed codes."""
    from raft_tpu.neighbors.ivf_pq import _calculate_pq_dim
    from raft_tpu.ops.pq_scan import book_tables, pq_fused_scan

    J, bits = _calculate_pq_dim(DIM), 8
    lo, hi = jax.eval_shape(
        lambda b: book_tables(b, bits),
        jax.ShapeDtypeStruct((J, 1 << bits, DIM // J), jnp.float32))
    max_cells, qrows = _cells()
    hlo = _compile(
        one_chip,
        lambda cl, q, codes, lo_, hi_, bad: pq_fused_scan(
            cl, q, codes, lo_, hi_, bad, K, J, bits, False),
        ((max_cells,), jnp.int32), ((max_cells, qrows, DIM), jnp.float32),
        ((N_LISTS, J * bits // 8, CAP), jnp.uint8),
        (lo.shape, lo.dtype), (hi.shape, hi.dtype),
        ((N_LISTS, CAP), jnp.bool_))
    assert "tpu_custom_call" in hlo


def test_stream_select_min(one_chip):
    """select_k's streaming engine at its large-k crossover shape."""
    from raft_tpu.matrix.select_k import _stream_select_min

    hlo = _compile(one_chip, lambda v: _stream_select_min(v, 128),
                   ((64, 131072), jnp.float32))
    assert "tpu_custom_call" in hlo
