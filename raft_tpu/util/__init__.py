"""Host-level utilities (ref: cpp/include/raft/util).

The reference's util layer is mostly warp/block SIMT machinery
(bitonic_sort, vectorized IO, shuffles) that has no user-visible analog on
TPU — XLA/Pallas own that level. What survives is the host-side arithmetic
used to shape launches and layouts.
"""

from raft_tpu.util.pow2 import Pow2, ceildiv, round_up_safe, round_down_safe, is_pow2
from raft_tpu.util.itertools import product_of_lists
from raft_tpu.util.input_validation import is_row_major, is_col_major

__all__ = [
    "Pow2",
    "ceildiv",
    "round_up_safe",
    "round_down_safe",
    "is_pow2",
    "product_of_lists",
    "is_row_major",
    "is_col_major",
]
