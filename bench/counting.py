"""The comm step's algorithmic bytes: what any implementation of
TAMUNA's exchange has to move through HBM, counted from shapes.

Per coordinate of the model (``d`` of them per client row): the ``s``
owners' values of ``x`` read once, those owners' ``h`` entries read and
written, and the DownCom's writes of ``xbar`` into every receiving row
(the next round's ``c`` cohort rows, every row under full
participation).  Rows and coordinates a client does not own are not
counted, and ``xbar`` itself is not counted apart from the rows it lands
in: a fused step never stores it, so the count stays a lower bound for
any implementation -- dense, workspace or kernels."""

from __future__ import annotations

from typing import Sequence

import numpy as np


def comm_bytes(dims: Sequence[int], *, n: int, c: int, s: int,
               x_bytes: int = 4, h_bytes: int = 4) -> float:
    d = float(np.sum(np.asarray(dims, np.float64)))
    down_rows = c if c < n else n
    return d * (s * x_bytes + 2 * s * h_bytes + down_rows * x_bytes)
