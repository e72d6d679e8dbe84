"""``comms_t``-style collective facade over XLA mesh collectives.

Ref: cpp/include/raft/core/comms.hpp:123-242 (``comms_iface``/``comms_t``:
get_size/get_rank/comm_split/barrier, allreduce, bcast, reduce, allgather,
allgatherv, gather, gatherv, reducescatter, device_send/recv/sendrecv,
group_start/end; ``datatype_t``/``op_t`` enums :33-34; ``status_t`` from
sync_stream :135) and the NCCL/UCX implementation comms/detail/std_comms.hpp.

TPU-native re-design (SURVEY.md §2.11 mapping): a communicator is a **mesh
axis**. Methods are designed to be called *inside* ``shard_map`` over a
``jax.sharding.Mesh`` — each maps 1:1 onto a lax collective riding ICI/DCN:

    allreduce      ⇔ lax.psum / pmin / pmax / pmean
    allgather      ⇔ lax.all_gather
    reducescatter  ⇔ lax.psum_scatter
    bcast          ⇔ all_gather + slice from root
    device_send/recv ⇔ lax.ppermute
    comm_split     ⇔ operating on a sub-axis of a multi-axis mesh

There is no NCCL bootstrap to perform: XLA compiles the collectives into the
program (multi-host bootstrap is ``jax.distributed.initialize``, the analog
of raft-dask's NCCL clique formation, raft_dask/common/comms.py:170).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class DatatypeT(enum.Enum):
    """Ref: comms_t::datatype_t (core/comms.hpp:33). JAX arrays carry their
    dtype; the enum is kept for API parity."""

    CHAR = 0
    UINT8 = 1
    INT32 = 2
    UINT32 = 3
    INT64 = 4
    UINT64 = 5
    FLOAT32 = 6
    FLOAT64 = 7


class OpT(enum.Enum):
    """Ref: comms_t::op_t (core/comms.hpp:34)."""

    SUM = 0
    PROD = 1
    MIN = 2
    MAX = 3


class StatusT(enum.Enum):
    """Ref: comms_t::status_t (core/comms.hpp:135) — sync_stream outcome."""

    SUCCESS = 0
    ERROR = 1
    ABORT = 2


@dataclass(frozen=True)
class Comms:
    """A communicator bound to one or more mesh axes.

    Use inside ``shard_map``: every collective lowers to an XLA op over the
    named axes. ``get_rank``/``get_size`` are trace-time collectives too
    (lax.axis_index / axis size), like the reference's per-rank views of one
    logical communicator (ref: comms_t facade, core/comms.hpp:242).
    """

    axis: Union[str, Sequence[str]] = "data"
    mesh: Optional[jax.sharding.Mesh] = None

    # -- topology ----------------------------------------------------------
    def get_size(self) -> int:
        """Ref: comms_t::get_size. Static when a mesh is bound."""
        if self.mesh is not None:
            axes = (self.axis,) if isinstance(self.axis, str) else tuple(self.axis)
            n = 1
            for a in axes:
                n *= self.mesh.shape[a]
            return n
        return lax.axis_size(self.axis)

    def get_rank(self):
        """Ref: comms_t::get_rank. Only meaningful inside shard_map."""
        return lax.axis_index(self.axis)

    def comm_split(self, axis: Union[str, Sequence[str]]) -> "Comms":
        """Sub-communicator over a different mesh axis (ref:
        comms_t::comm_split, core/comms.hpp — the reference re-bootstraps
        NCCL; here a sub-axis of the mesh IS the split)."""
        return Comms(axis=axis, mesh=self.mesh)

    def barrier(self) -> None:
        """Ref: comms_t::barrier. XLA programs are data-flow ordered; an
        explicit barrier is a no-op inside a compiled program."""

    def sync_stream(self, *arrays) -> StatusT:
        """Ref: comms_t::sync_stream (status-returning async-error probe,
        core/comms.hpp:290). Cooperative cancellation (interruptible.cancel)
        surfaces as ABORT — the role of the reference's
        ncclCommAbort-triggered status — while XLA/collective failures
        surface as ERROR. A raw KeyboardInterrupt (ctrl-C outside the
        cooperative chain) propagates: swallowing it would let callers that
        ignore the returned status spin forever.
        """
        from raft_tpu.core import interruptible
        from raft_tpu.core.interruptible import InterruptedException

        try:
            # interruptible.synchronize polls the thread's cancellation
            # token while waiting, so cancel()/cancel_thread() can actually
            # surface here (a raw block_until_ready never observes it).
            interruptible.synchronize(*arrays)
            return StatusT.SUCCESS
        except InterruptedException:
            return StatusT.ABORT
        except Exception:  # XLA surfaces collective failures as exceptions
            return StatusT.ERROR

    def group_start(self) -> None:
        """Ref: comms_t::group_start (→ ncclGroupStart). The reference
        batches collective launches to avoid deadlock/serialization; XLA
        schedules all collectives of a compiled program jointly, so the
        grouping is implicit — kept as a no-op for API parity."""

    def group_end(self) -> None:
        """Ref: comms_t::group_end (→ ncclGroupEnd). See group_start."""

    # -- collectives (call inside shard_map) -------------------------------
    def allreduce(self, x, op: OpT = OpT.SUM):
        """Ref: comms_t::allreduce (core/comms.hpp:344 → ncclAllReduce)."""
        if op == OpT.SUM:
            return lax.psum(x, self.axis)
        if op == OpT.MIN:
            return lax.pmin(x, self.axis)
        if op == OpT.MAX:
            return lax.pmax(x, self.axis)
        if op == OpT.PROD:
            # Exact elementwise product across ranks: gather the rank values
            # and multiply. (A log/exp psum trick would NaN on negatives and
            # lose zeros; all_gather+prod preserves sign/zero semantics of
            # ncclProd exactly. Product reductions are rare and small, so
            # the size-x traffic of the gather is acceptable.)
            stacked = lax.all_gather(x, self.axis)  # (size, ...)
            return jnp.prod(stacked, axis=0)
        raise ValueError(op)

    def allgather(self, x, axis: int = 0, tiled: bool = True):
        """Ref: comms_t::allgather → ncclAllGather. Returns the concatenation
        over ranks along ``axis`` (``tiled=False`` stacks a new axis)."""
        return lax.all_gather(x, self.axis, axis=axis, tiled=tiled)

    def allgatherv(self, x, counts, axis: int = 0):
        """Ref: comms_t::allgatherv. Under static shapes, shards are padded
        to the max count by the caller; this gathers the padded shards plus
        their counts so the caller can mask."""
        return (lax.all_gather(x, self.axis, axis=axis, tiled=True),
                lax.all_gather(counts, self.axis))

    def reduce(self, x, root: int = 0, op: OpT = OpT.SUM):
        """Ref: comms_t::reduce → ncclReduce. All ranks compute the sum (XLA
        collectives are symmetric); non-root ranks get zeros like the
        reference leaves their buffers unspecified."""
        full = self.allreduce(x, op)
        return jnp.where(lax.axis_index(self.axis) == root, full,
                         jnp.zeros_like(full))

    def bcast(self, x, root: int = 0):
        """Ref: comms_t::bcast → ncclBroadcast."""
        stacked = lax.all_gather(x, self.axis)  # (size, ...)
        return stacked[root]

    def reducescatter(self, x, op: OpT = OpT.SUM, scatter_axis: int = 0):
        """Ref: comms_t::reducescatter → ncclReduceScatter."""
        if op != OpT.SUM:
            raise ValueError("reducescatter supports SUM (like psum_scatter)")
        return lax.psum_scatter(x, self.axis, scatter_dimension=scatter_axis,
                                tiled=True)

    def gather(self, x, root: int = 0, axis: int = 0):
        """Ref: comms_t::gather. SPMD XLA has no asymmetric gather — the
        all_gather traffic lands everywhere — but the *contract* is rooted:
        non-root ranks get zeros so callers cannot accidentally depend on
        data the reference leaves unspecified off-root."""
        full = lax.all_gather(x, self.axis, axis=axis, tiled=True)
        return jnp.where(lax.axis_index(self.axis) == root, full,
                         jnp.zeros_like(full))

    def gatherv(self, x, count, root: int = 0, axis: int = 0):
        """Ref: comms_t::gatherv (core/comms.hpp:200-240) — root receives a
        variable-length shard from each rank. Under static shapes each rank
        sends its padded shard plus its valid ``count``; the root gets
        ``(stacked (size, pad, ...), counts (size,))`` and masks/compacts.
        Root-only semantics: non-root ranks receive zeros (see ``gather``).
        """
        stacked = lax.all_gather(x, self.axis, axis=axis, tiled=False)
        counts = lax.all_gather(count, self.axis)
        is_root = lax.axis_index(self.axis) == root
        return (jnp.where(is_root, stacked, jnp.zeros_like(stacked)),
                jnp.where(is_root, counts, jnp.zeros_like(counts)))

    def device_sendrecv(self, x, dest: int, source: int):
        """Paired send/recv (ref: comms_t::device_sendrecv,
        core/comms.hpp) — expressed as a ppermute over the send edges."""
        size = self.get_size() if self.mesh is not None else lax.axis_size(self.axis)
        perm = [(i, (i + dest - source) % size) for i in range(size)]
        return lax.ppermute(x, self.axis, perm)

    def shift(self, x, offset: int = 1):
        """Ring shift by ``offset`` (the ppermute idiom behind
        neighbor exchanges)."""
        size = self.get_size() if self.mesh is not None else lax.axis_size(self.axis)
        perm = [(i, (i + offset) % size) for i in range(size)]
        return lax.ppermute(x, self.axis, perm)

    def device_multicast_sendrecv(self, x, axis: int = 0):
        """Per-rank multi-destination exchange (ref:
        comms_t::device_multicast_sendrecv, core/comms.hpp:218): slab j
        of ``x`` along ``axis`` is this rank's payload for rank j; the
        result has slab j = what rank j sent to this rank. The reference
        issues a vector of paired NCCL send/recvs inside a group; on the
        mesh the whole pattern is ONE XLA all_to_all riding ICI/DCN.
        Ragged per-destination sizes (the sendsizes/sendoffsets vectors)
        pad to the max slab — XLA's static shapes, same convention as
        gatherv."""
        return lax.all_to_all(x, self.axis, split_axis=axis,
                              concat_axis=axis, tiled=True)

    def host_sendrecv(self, x, dest: int, source: int, retry=None,
                      transfer_hook=None):
        """Paired HOST-buffer send/recv (ref: the host point-to-point
        role of comms_t::isend/irecv/waitall, core/comms.hpp:137-141 —
        UCX-tagged transfers between rank host buffers, e.g. raft-dask
        control payloads). ``x`` is a host array whose leading axis is
        the per-rank send buffer (row r = rank r's payload); returns the
        same layout with row r = what rank r received. The buffer hops
        through the devices: staged sharded, one ppermute over the same
        edge set as device_sendrecv (cross-host edges ride DCN under
        jax.distributed), fetched back to host. Eager helper — call it
        OUTSIDE shard_map bodies. One-sided *tagged* isend/irecv have no
        mesh analog (no rendezvous peer in a single-controller program);
        this paired form covers the transfer role — see docs/api_map.md.

        ``retry``: optional :class:`raft_tpu.core.retry.RetryPolicy` —
        this is an eager host transfer (stage → ppermute → fetch), the
        kind of op that can transiently fail on a multi-host DCN and
        succeed on re-attempt; a policy wraps the whole round-trip in
        :func:`~raft_tpu.core.retry.with_retry` (deterministic backoff,
        cause-chained re-raise). ``transfer_hook`` is a test seam (the
        chaos harness wraps it) applied around one attempt's transfer.
        """
        from raft_tpu.core.error import expects
        from raft_tpu.core.retry import with_retry

        expects(self.mesh is not None,
                "host_sendrecv needs a mesh-bound Comms (build_comms)")
        x = np.asarray(x)
        expects(x.shape[0] == self.get_size(),
                "leading axis must equal the comm size (one row per rank)")
        sharding = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(self.axis))

        def transfer():
            # make_array_from_callback, not device_put: on a multi-process
            # (jax.distributed) mesh each process can only place its own
            # addressable shards.
            xd = jax.make_array_from_callback(x.shape, sharding,
                                              lambda idx: x[idx])
            fn = jax.jit(jax.shard_map(
                lambda v: self.device_sendrecv(v, dest, source),
                mesh=self.mesh,
                in_specs=jax.sharding.PartitionSpec(self.axis),
                out_specs=jax.sharding.PartitionSpec(self.axis),
                check_vma=False))
            out = fn(xd)
            # Rows addressable to THIS process (all rows on a single-
            # process mesh) — a process cannot read its peers' host
            # buffers, same as the reference's per-rank recv buffers.
            shards = sorted(out.addressable_shards,
                            key=lambda s: s.index[0].start or 0)
            return np.concatenate([np.asarray(s.data) for s in shards])

        op = transfer if transfer_hook is None else transfer_hook(transfer)
        if retry is None:
            return op()
        return with_retry(op, retry)


def build_comms(mesh: jax.sharding.Mesh, axis: str = "data") -> Comms:
    """Factory (ref: build_comms_nccl_only, comms/std_comms.hpp:67 — but
    there is nothing to bootstrap: the mesh IS the clique)."""
    return Comms(axis=axis, mesh=mesh)


def inject_comms_on_handle(handle, comms: Comms) -> None:
    """Attach a communicator to a Resources handle (ref:
    raft_dask inject_comms_on_handle, comms_utils.pyx:288 →
    handle.set_comms)."""
    handle.set_comms(comms)
