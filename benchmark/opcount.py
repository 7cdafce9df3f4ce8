"""Int32 vector operations a verify kernel needs, from its shapes: the
op-count model of PROFILE.md section 1, kept here for the kernel roofline
share that waits on a published int32 VPU peak for v5e (no reader uses it
yet; see PERF.md section 7).

One field multiply (schoolbook over 20 limbs of 13 bits): 400 multiplies,
~380 adds for the anti-diagonal fold, ~420 shift/mask/adds to carry:
~1,200 ops per element.  A point add (unified, extended coordinates) is
9 multiplies, ~10.8k ops; a point double ~8.7k ops.
"""

from __future__ import annotations

import math

FIELD_MUL_OPS = 1_200
POINT_ADD_OPS = 9 * FIELD_MUL_OPS        # 10,800
POINT_DOUBLE_OPS = 8_700
ROWCOMBINED_POINT_OPS_PER_ROW = 570      # the per-row combined check


def combined_ops(rows: int) -> float:
    """The per-row combined check over ``rows`` rows (~6.2M ops a row)."""
    return rows * ROWCOMBINED_POINT_OPS_PER_ROW * POINT_ADD_OPS


def pippenger_ops(rows: int, c: int) -> float:
    """The Pippenger MSM over the 4n+2 terms of ``rows`` rows with window
    ``c``: m(K+1) bucket adds for K = ceil(253/c) windows, plus the window
    reduction (2^c adds and c doubles per window)."""
    m = 4 * rows + 2
    k = math.ceil(253 / c)
    adds = m * (k + 1) + k * (2 ** (c + 1))
    return adds * POINT_ADD_OPS + k * c * POINT_DOUBLE_OPS
