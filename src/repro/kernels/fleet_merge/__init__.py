"""Fleet-wide merge remainder: one dispatch upserts every view's deltas.

See ref.py for semantics, kernel.py for the Pallas tiling, ops.py for
the public ``fleet_merge`` dispatch.
"""

from .kernel import BLOCK_G, BLOCK_R, BLOCK_V, fleet_merge_tiles
from .ops import fleet_merge
from .ref import delta_only_rows, fleet_merge_ref

__all__ = [
    "BLOCK_G",
    "BLOCK_R",
    "BLOCK_V",
    "delta_only_rows",
    "fleet_merge",
    "fleet_merge_ref",
    "fleet_merge_tiles",
]
