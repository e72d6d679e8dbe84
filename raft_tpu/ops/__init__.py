"""Pallas TPU kernels for the hot ops.

The reference implements its performance-critical inner loops as hand-tiled
CUDA kernels (fused_l2_knn.cuh, select_warpsort.cuh/select_radix.cuh, the
IVF-PQ compute_similarity_kernel). On TPU the analogous wins come from
Pallas kernels that keep tiles in VMEM, feed the MXU with the gram work and
fold the selection into the same pass so the big intermediate (the
n_queries × n_db distance matrix, the per-probe score matrix) never reaches
HBM. Everything here has an XLA fallback in its caller; kernels are used
when the backend is TPU (or explicitly, in interpret mode, for tests).
"""

import jax

from raft_tpu.ops.fused_knn import fused_knn, fused_knn_supported


def pallas_interpret() -> bool:
    """Interpret mode for the kernels a caller dispatches: never on a
    TPU, where they compile to Mosaic, and always elsewhere (the CPU
    test mesh), where the interpreter is the only way they run."""
    return jax.default_backend() != "tpu"


__all__ = [
    "fused_knn",
    "fused_knn_supported",
    "pallas_interpret",
]
