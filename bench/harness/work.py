"""The arithmetic of the work counts that does not depend on the
architecture.  Each configuration's reference module
(``bench/references/<name>.py``) counts its own operations and bytes from
the configuration's logical shapes and each slot's actual context, never
from padded shapes or the pool's capacity.

The least time of a piece of work is the larger of its operations over
the chip's int8 peak and its bytes over the HBM bandwidth.
"""

from __future__ import annotations

from typing import Dict


def least_time(ops: float, nbytes: float, peaks: Dict) -> float:
    return max(ops / peaks["int8_ops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def packed_len(length: int, page: int) -> int:
    """Positions a decode step reads from packed pages: the completed
    blocks of a slot holding ``length`` positions."""
    return (length // page) * page
