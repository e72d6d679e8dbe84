"""Built-in collective self-tests.

Ref: cpp/include/raft/comms/comms_test.hpp (171 LoC wrappers) →
comms/detail/test.hpp (544 LoC): ``test_collective_allreduce`` etc., each
returning bool; the reference drives them from Python over a
LocalCUDACluster (raft_dask/test/test_comms.py:26-160). Here they run over
any ``jax.sharding.Mesh`` — the virtual CPU-device mesh used in CI, the
real chip mesh, or a **multi-process** mesh bootstrapped with
``jax.distributed`` (tests/test_multiprocess_comms.py): inputs are placed
as global arrays and each process verifies only the shards it owns, so
the same functions prove both the SPMD semantics and the DCN bootstrap.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tpu.comms.comms import Comms, OpT


def _run(mesh: Mesh, axis: str, fn, in_spec, out_spec, *args):
    sm = jax.shard_map(fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
                       check_vma=False)
    return jax.jit(sm)(*args)


def _zeros(mesh: Mesh, shape, spec):
    """Global zeros placed over the mesh — multi-process safe (a plain
    ``jnp.zeros`` is process-local and cannot feed a multi-host
    shard_map)."""
    g = np.zeros(shape, np.float32)
    return jax.make_array_from_callback(
        shape, NamedSharding(mesh, spec), lambda idx: g[idx])


def _check(out, expect: np.ndarray, atol: float = 1e-6) -> bool:
    """Verify the addressable shards of a global output against the
    expected *global* array — each process checks what it owns (in a
    single process that is everything)."""
    for s in out.addressable_shards:
        if not np.allclose(np.asarray(s.data), expect[s.index], atol=atol):
            return False
    return True


def test_collective_allreduce(mesh: Mesh, axis: str = "data") -> bool:
    """Each rank contributes 1; result must equal world size
    (ref: comms/detail/test.hpp test_collective_allreduce)."""
    n = mesh.shape[axis]
    comms = Comms(axis=axis, mesh=mesh)

    def body(x):
        return comms.allreduce(jnp.ones((1,), jnp.float32))

    out = _run(mesh, axis, body, (P(axis),), P(axis),
               _zeros(mesh, (n,), P(axis)))
    return _check(out, np.full((n,), n, np.float32))


def test_collective_allreduce_prod(mesh: Mesh, axis: str = "data") -> bool:
    """PROD with negatives and a zero lane: rank r contributes
    [-(r+2), r==0 ? 0 : 1], so lane 0 must be (-1)^n * (n+1)!/1! and lane 1
    must be 0 (sign/zero semantics of ncclProd)."""
    n = mesh.shape[axis]
    comms = Comms(axis=axis, mesh=mesh)

    def body(x):
        r = comms.get_rank()
        mine = jnp.stack([-(r.astype(jnp.float32) + 2.0),
                          jnp.where(r == 0, 0.0, 1.0)])
        return comms.allreduce(mine, op=OpT.PROD)[None]

    out = _run(mesh, axis, body, (P(axis),), P(axis, None),
               _zeros(mesh, (n,), P(axis)))
    expect0 = ((-1.0) ** n) * np.prod(np.arange(2, n + 2, dtype=np.float64))
    expect = np.zeros((n, 2), np.float32)
    expect[:, 0] = expect0
    return _check(out, expect, atol=1e-3)


def test_collective_gatherv(mesh: Mesh, axis: str = "data",
                            root: int = 0) -> bool:
    """Rooted variable-count gather: rank r sends r+1 valid values (padded
    to the max); root must see every shard with its count, non-root must
    see zeros (ref: test_collective_gatherv, comms/detail/test.hpp)."""
    n = mesh.shape[axis]
    comms = Comms(axis=axis, mesh=mesh)
    pad = n  # max count

    def body(x):
        r = comms.get_rank()
        cnt = r + 1
        mine = jnp.where(jnp.arange(pad) < cnt,
                         r.astype(jnp.float32) + 10.0, 0.0)
        shards, counts = comms.gatherv(mine, cnt[None], root=root)
        return shards.reshape(-1)[None], counts.reshape(-1)[None]

    shards, counts = _run(mesh, axis, body, (P(axis),),
                          (P(axis, None), P(axis, None)),
                          _zeros(mesh, (n,), P(axis)))
    shards_exp = np.zeros((n, n, pad), np.float32)
    counts_exp = np.zeros((n, n), np.float32)
    for src in range(n):
        shards_exp[root, src, :src + 1] = src + 10.0
        counts_exp[root, src] = src + 1
    return (_check(shards, shards_exp.reshape(n, n * pad))
            and _check(counts, counts_exp))


def test_collective_allgatherv(mesh: Mesh, axis: str = "data") -> bool:
    """Padded variable-count allgather: every rank sees every shard plus
    its valid count (ref: test_collective_allgatherv,
    comms/detail/test.hpp — padded shards + counts, caller masks)."""
    n = mesh.shape[axis]
    comms = Comms(axis=axis, mesh=mesh)
    pad = n  # max count

    def body(x):
        r = comms.get_rank()
        cnt = r + 1
        mine = jnp.where(jnp.arange(pad) < cnt,
                         r.astype(jnp.float32) + 10.0, 0.0)
        shards, counts = comms.allgatherv(mine, cnt[None])
        return shards.reshape(-1)[None], counts.reshape(-1)[None]

    shards, counts = _run(mesh, axis, body, (P(axis),),
                          (P(axis, None), P(axis, None)),
                          _zeros(mesh, (n,), P(axis)))
    shards_exp = np.zeros((n, n, pad), np.float32)
    counts_exp = np.zeros((n, n), np.float32)
    for src in range(n):
        shards_exp[:, src, :src + 1] = src + 10.0
        counts_exp[:, src] = src + 1
    return (_check(shards, shards_exp.reshape(n, n * pad))
            and _check(counts, counts_exp))


def test_collective_gather(mesh: Mesh, axis: str = "data",
                           root: int = 0) -> bool:
    """Rooted gather: root sees every rank's value concatenated, non-root
    ranks see zeros (ref: test_collective_gather,
    comms/detail/test.hpp)."""
    n = mesh.shape[axis]
    comms = Comms(axis=axis, mesh=mesh)

    def body(x):
        mine = comms.get_rank().astype(jnp.float32)[None] + 5.0
        return comms.gather(mine, root=root)[None]

    out = _run(mesh, axis, body, (P(axis),), P(axis, None),
               _zeros(mesh, (n,), P(axis)))
    expect = np.zeros((n, n), np.float32)
    expect[root] = np.arange(n, dtype=np.float32) + 5.0
    return _check(out, expect)


def test_collective_broadcast(mesh: Mesh, axis: str = "data", root: int = 0) -> bool:
    """Root's value must land on every rank (ref: test_collective_bcast)."""
    n = mesh.shape[axis]
    comms = Comms(axis=axis, mesh=mesh)

    def body(x):
        mine = jnp.where(comms.get_rank() == root, 7.0, 0.0)[None]
        return comms.bcast(mine, root=root)

    out = _run(mesh, axis, body, (P(axis),), P(axis),
               _zeros(mesh, (n,), P(axis)))
    return _check(out, np.full((n,), 7.0, np.float32))


def test_collective_reduce(mesh: Mesh, axis: str = "data", root: int = 0) -> bool:
    """Ref: test_collective_reduce — only root holds the sum."""
    n = mesh.shape[axis]
    comms = Comms(axis=axis, mesh=mesh)

    def body(x):
        return comms.reduce(jnp.ones((1,), jnp.float32), root=root)

    out = _run(mesh, axis, body, (P(axis),), P(axis),
               _zeros(mesh, (n,), P(axis)))
    expect = np.zeros((n,), np.float32)
    expect[root] = n
    return _check(out, expect)


def test_collective_allgather(mesh: Mesh, axis: str = "data") -> bool:
    """Ref: test_collective_allgather — every rank sees [0..n)."""
    n = mesh.shape[axis]
    comms = Comms(axis=axis, mesh=mesh)

    def body(x):
        mine = comms.get_rank().astype(jnp.float32)[None]
        return comms.allgather(mine)[None]

    out = _run(mesh, axis, body, (P(axis),), P(axis, None),
               _zeros(mesh, (n,), P(axis)))
    expect = np.arange(n, dtype=np.float32)[None, :].repeat(n, 0)
    return _check(out, expect)


def test_collective_reducescatter(mesh: Mesh, axis: str = "data") -> bool:
    """Ref: test_collective_reducescatter — each rank gets its slice of the
    elementwise sum."""
    n = mesh.shape[axis]
    comms = Comms(axis=axis, mesh=mesh)

    def body(x):
        contrib = jnp.ones((n,), jnp.float32)
        return comms.reducescatter(contrib)

    out = _run(mesh, axis, body, (P(axis),), P(axis),
               _zeros(mesh, (n,), P(axis)))
    return _check(out, np.full((n,), n, np.float32))


def test_pointToPoint_simple_send_recv(mesh: Mesh, axis: str = "data") -> bool:
    """Ring exchange: rank r sends its id to r+1 (ref:
    test_pointToPoint_simple_send_recv over UCX; here a ppermute)."""
    n = mesh.shape[axis]
    comms = Comms(axis=axis, mesh=mesh)

    def body(x):
        mine = comms.get_rank().astype(jnp.float32)[None]
        return comms.shift(mine, 1)

    out = _run(mesh, axis, body, (P(axis),), P(axis),
               _zeros(mesh, (n,), P(axis)))
    expect = ((np.arange(n) - 1) % n).astype(np.float32)
    return _check(out, expect)


def test_pointToPoint_device_multicast_sendrecv(mesh: Mesh,
                                                axis: str = "data") -> bool:
    """All-pairs multicast: rank r sends payload r·n+j to rank j (ref:
    test_pointToPoint_device_multicast_sendrecv — a NCCL send/recv
    group; here one all_to_all). Rank r must end with column r of the
    payload matrix."""
    n = mesh.shape[axis]
    comms = Comms(axis=axis, mesh=mesh)

    def body(x):
        r = comms.get_rank().astype(jnp.float32)
        mine = r * n + jnp.arange(n, dtype=jnp.float32)  # (n,) slab j → rank j
        return comms.device_multicast_sendrecv(mine[:, None], axis=0)[None]

    out = _run(mesh, axis, body, (P(axis),), P(axis),
               _zeros(mesh, (n,), P(axis)))
    expect = (np.arange(n)[:, None] * 0 + np.arange(n)[None, :] * n
              + np.arange(n)[:, None]).astype(np.float32)[..., None]
    return _check(out, expect)


def test_pointToPoint_host_sendrecv(mesh: Mesh, axis: str = "data") -> bool:
    """Host-buffer paired send/recv: the eager facade must route each
    rank's host row through the device edge set and land the permuted
    rows back on the host (ref: the UCX host p2p role of isend/irecv)."""
    n = mesh.shape[axis]
    comms = Comms(axis=axis, mesh=mesh)
    payload = np.arange(n, dtype=np.float32)[:, None] * 10.0
    out = comms.host_sendrecv(payload, dest=1, source=0)
    expect = payload[(np.arange(n) - 1) % n]
    return bool(np.allclose(out, expect))


def test_commsplit(mesh2d: Mesh, row_axis: str = "rows",
                   col_axis: str = "cols") -> bool:
    """Sub-communicator over one axis of a 2-D mesh (ref: test_commsplit —
    NCCL re-bootstrap; here the sub-axis psum must count only that axis)."""
    nr, nc = mesh2d.shape[row_axis], mesh2d.shape[col_axis]
    comms = Comms(axis=(row_axis, col_axis), mesh=mesh2d)
    sub = comms.comm_split(col_axis)

    def body(x):
        return sub.allreduce(jnp.ones((1, 1), jnp.float32))

    sm = jax.shard_map(body, mesh=mesh2d, in_specs=(P(row_axis, col_axis),),
                       out_specs=P(row_axis, col_axis), check_vma=False)
    out = jax.jit(sm)(_zeros(mesh2d, (nr, nc), P(row_axis, col_axis)))
    return _check(out, np.full((nr, nc), nc, np.float32))
