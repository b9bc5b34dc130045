"""Per-round FLOPs and logical bytes of the round's Pallas kernels.

Counted from the trainable leaves' shapes, unpadded: a kernel that pads
a one-row leaf up to its block reads more than this, and its roofline
share shows the waste. Bytes count each operand once per call as the
round needs it: with stacked clients (``mode="vmap"``) the global model
is one operand of one call for all K clients; with sequential clients
(``mode="scan"``) each client's call reads it again.

- ``sqdiff_rowsum`` (Eq. 3, ``kernels/divergence.py``): per leaf, reads
  the K local leaves and the global leaf, writes one f32 per unit row;
  3 FLOPs per element (subtract, multiply, add).
- ``fused_uplink_ef`` (packed uplink with error feedback,
  ``kernels/uplink.py``): per leaf, reads int8 levels, f32 ``v`` and the
  old residual for K clients plus three f32 per client and unit row,
  writes the f32 numerator and K new residual rows; 8 FLOPs per element.

A roofline metric of its own (``bench/metrics/<kernel>_roofline.py``)
calls its kernel's function here with the run's trainable shapes and
traffic, and names the substring that marks the kernel's events in the
device trace; a new kernel brings a new function in a new file under
``bench/costs/``.
"""
from __future__ import annotations

import numpy as np

from bench.fedref import STACKED, leaf_items


def _leaves(trainable):
    """(rows, elements, itemsize) per trainable leaf."""
    out = []
    for key in sorted(trainable):
        for _, leaf in leaf_items(trainable[key]):
            rows = leaf.shape[0] if key in STACKED else 1
            out.append((rows, int(np.prod(leaf.shape)),
                        np.dtype(leaf.dtype).itemsize))
    return out


def sqdiff_rowsum(trainable, k: int, mode: str) -> dict:
    flops = byts = 0.0
    for rows, size, item in _leaves(trainable):
        glob_reads = 1 if mode == "vmap" else k
        flops += 3.0 * k * size
        byts += (k + glob_reads) * size * item + k * rows * 4
    return {"flops": flops, "bytes": byts}


def fused_uplink_ef(trainable, k: int) -> dict:
    flops = byts = 0.0
    for rows, size, item in _leaves(trainable):
        flops += 8.0 * k * size
        byts += (k * size * (1 + 4 + item + 4) + size * 4
                 + 3 * k * rows * 4)
    return {"flops": flops, "bytes": byts}

