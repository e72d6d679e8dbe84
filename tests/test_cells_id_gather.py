"""The cells engines look ids up after the final selection.

``_cells_scan_probes`` (IVF-Flat) and ``_compressed_scan_probes``
(IVF-PQ) route flat slot positions through the per-query merge and read
the id table only for the q·k winners. The reference below is the
formula both used before: gather every kernel candidate's id, then route
and select the ids. The answers must be bit-identical, since select_k
ranks by value and breaks ties by position, never by the payload.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

from raft_tpu.matrix.select_k import select_k
from raft_tpu.neighbors import ivf_flat, ivf_pq
from raft_tpu.neighbors.ivf_flat import _route_candidates_cells


def _early_gather_reference(bd_, bi_, cell_list, indices, route, q, p, k,
                            scope):
    """Every candidate's id first, then the merge (the former formula)."""
    gi = indices[jnp.maximum(cell_list, 0)[:, None, None],
                 jnp.maximum(bi_, 0)]
    gi = jnp.where(bi_ < 0, -1, gi)
    cd, ci = _route_candidates_cells(bd_, gi, route, q, p)
    return select_k(cd, k, select_min=True, indices=ci)


def _ids(rng, shape, dtype):
    """Unique ids in no particular order, so a tie broken by id would
    differ from one broken by position; past 2**32 for int64."""
    base = (1 << 32) if dtype == jnp.int64 else 0
    ids = rng.permutation(int(np.prod(shape))).reshape(shape) * 7 + base
    return jnp.asarray(ids, dtype)


def _both(monkeypatch, module, fn):
    """``fn()`` with the late gather, then with the reference in its
    place. Each is traced afresh: jit would reuse its trace of ``fn``
    itself, so each call wraps it in a new function."""
    new = jax.jit(lambda: fn())()
    monkeypatch.setattr(module, "_select_cells_ids",
                        _early_gather_reference)
    old = jax.jit(lambda: fn())()
    return [np.asarray(a) for a in new + old]


def _check(d, i, d0, i0, k, id_dtype):
    assert i.dtype == np.dtype(id_dtype) == i0.dtype
    np.testing.assert_array_equal(d, d0)
    np.testing.assert_array_equal(i, i0)
    # The cases the fixture was built to reach: sentinels from starved
    # probes, and exact ties among the answers.
    assert (i == -1).any() and np.isinf(d[i == -1]).all()
    fin = np.where(np.isfinite(d), d, np.nan)
    assert (fin[:, 1:] == fin[:, :-1]).any()
    assert i.shape[1] == k


@pytest.mark.parametrize("id_dtype", [jnp.int32, jnp.int64])
@pytest.mark.parametrize("inner_is_l2", [True, False], ids=["l2", "ip"])
def test_cells_scan_probes_matches_early_gather(monkeypatch, rng,
                                                inner_is_l2, id_dtype):
    n_lists, cap, dim, q, p, k = 8, 128, 8, 32, 3, 10
    with jax.enable_x64(id_dtype == jnp.int64):
        # Small integer values: distances are exact, so duplicate rows
        # and equal distances tie exactly.
        data = rng.integers(0, 3, size=(n_lists, cap, dim))
        data[:, 64:] = data[:, :64]                # duplicate rows
        data = jnp.asarray(data, jnp.float32)
        Q = jnp.asarray(rng.integers(0, 3, size=(q, dim)), jnp.float32)
        list_sizes = jnp.asarray([128, 3, 0, 100, 2, 128, 1, 77],
                                 jnp.int32)
        deleted = jnp.asarray(rng.random((n_lists, cap)) < 0.2)
        probes = np.stack([rng.permutation(n_lists)[:p] for _ in range(q)])
        probes[:4] = [1, 2, 6]    # 3 + 0 + 1 rows at most: fewer than k
        probe_ids = jnp.asarray(probes, jnp.int32)
        indices = _ids(rng, (n_lists, cap), id_dtype)

        def run():
            return ivf_flat._cells_scan_probes(
                Q, probe_ids, data, indices, list_sizes, k, inner_is_l2,
                8, False, interpret=True, deleted=deleted)

        d, i, d0, i0 = _both(monkeypatch, ivf_flat, run)
    _check(d, i, d0, i0, k, id_dtype)


@pytest.fixture(scope="module")
def pq_indexes():
    """One small IVF-PQ index per metric over rows with duplicates
    (equal codes in one list score alike)."""
    from raft_tpu.distance.distance_types import DistanceType

    rng = np.random.default_rng(3)
    db = rng.normal(size=(1000, 16)).astype(np.float32)
    db = np.concatenate([db, db])
    out = {}
    for is_ip in (False, True):
        metric = (DistanceType.InnerProduct if is_ip
                  else DistanceType.L2Expanded)
        out[is_ip] = (db, ivf_pq.build(
            ivf_pq.IndexParams(n_lists=8, kmeans_n_iters=3, pq_dim=8,
                               metric=metric), db))
    return out


@pytest.mark.parametrize("id_dtype", [jnp.int32, jnp.int64])
@pytest.mark.parametrize("is_ip", [False, True], ids=["l2", "ip"])
def test_compressed_scan_probes_matches_early_gather(monkeypatch, rng,
                                                     pq_indexes, is_ip,
                                                     id_dtype):
    from raft_tpu.ops.pq_scan import permute_subspaces

    db, idx = pq_indexes[is_ip]
    q, p, k = 32, 3, 10
    codesT, lo, hi, invalid, crot_p = idx.compressed_scan_operands()
    n_lists, cap = idx.indices.shape
    with jax.enable_x64(id_dtype == jnp.int64):
        # Tombstones, and three lists cut to 2, 0 and 1 live rows.
        shape = invalid.shape
        inv = np.asarray(invalid).reshape(n_lists, -1).copy()
        inv |= rng.random(inv.shape) < 0.2
        inv[1] |= np.arange(inv.shape[1]) >= 2
        inv[2] = True
        inv[4] |= np.arange(inv.shape[1]) >= 1
        invalid = jnp.asarray(inv).reshape(shape)
        probes = np.stack([rng.permutation(n_lists)[:p] for _ in range(q)])
        probes[:4] = [1, 2, 4]    # 2 + 0 + 1 rows at most: fewer than k
        probe_ids = jnp.asarray(probes, jnp.int32)
        indices = _ids(rng, (n_lists, cap), id_dtype)
        rotq_p = permute_subspaces(
            jnp.matmul(jnp.asarray(db[:q]), idx.rotation_matrix.T),
            idx.pq_dim, idx.pq_bits)
        # Zero queries: every inner product is 0, so ties span lists.
        rotq_p = rotq_p.at[8:12].set(0)

        def run():
            return ivf_pq._compressed_scan_probes(
                rotq_p, probe_ids, codesT, lo, hi, invalid, indices,
                crot_p, k, is_ip, idx.pq_dim, idx.pq_bits, 8, True)

        d, i, d0, i0 = _both(monkeypatch, ivf_pq, run)
    _check(d, i, d0, i0, k, id_dtype)


def _gathers_from(jaxpr, tainted):
    """Output sizes of every gather whose operand is derived from a
    tainted variable by reshapes and casts, through nested jaxprs."""
    passthrough = {"reshape", "squeeze", "expand_dims",
                   "convert_element_type", "copy", "copy_p"}
    tainted, sizes = set(tainted), []
    for eqn in jaxpr.eqns:
        op = eqn.invars[0] if eqn.invars else None
        hit = not isinstance(op, (Literal, type(None))) and op in tainted
        if eqn.primitive.name == "gather" and hit:
            sizes.append(int(np.prod(eqn.outvars[0].aval.shape)))
        elif eqn.primitive.name in passthrough and hit:
            tainted.update(eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            aligned = len(sub.invars) == len(eqn.invars)
            inner = [iv for ov, iv in zip(eqn.invars, sub.invars)
                     if aligned and not isinstance(ov, Literal)
                     and ov in tainted]
            sizes += _gathers_from(sub, inner)
    return sizes


def test_cells_search_gathers_ids_for_winners_only():
    """At the benchmark's shapes (SIFT-1M IVF-Flat: 1024 lists of 4096
    slots, dim 128, 1024 queries, 32 probes, k 10), every gather from
    the id table yields at most q·k ids: the table is never read per
    candidate (max_cells·64·k of them)."""
    from raft_tpu.neighbors.ivf_flat import _CELL_QROWS, _cells_search

    q, n_lists, cap, dim, p, k = 1024, 1024, 4096, 128, 32, 10
    f32, i32 = jnp.float32, jnp.int32
    args = (jax.ShapeDtypeStruct((q, dim), f32),
            jax.ShapeDtypeStruct((n_lists, dim), f32),
            jax.ShapeDtypeStruct((n_lists, cap, dim), f32),
            jax.ShapeDtypeStruct((n_lists, cap), i32),
            jax.ShapeDtypeStruct((n_lists,), i32))
    closed = jax.make_jaxpr(_cells_search, static_argnums=tuple(range(5, 12)))(
        *args, p, k, True, False, _CELL_QROWS, False, False)
    sizes = _gathers_from(closed.jaxpr, [closed.jaxpr.invars[3]])
    assert sizes, "no gather reads the id table"
    assert max(sizes) <= q * k, sizes
