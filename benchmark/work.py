"""The least work of one serving window, counted from the cell's true
sizes, whatever implements it, and the table of device peaks.

A driver request is one window of one segment: its pending earlier
drivers and its own application as rows. The window must read the
node state once at the true node count and write the committed
availability back, and read and write each row once.
"""

from __future__ import annotations

import json
import os

I32 = 4
NODE_READ = (
    3 * I32  # availability (cpu, memory, gpu)
    + 3 * I32  # schedulable
    + I32  # zone id
    + 2  # driver-candidate and domain masks, one byte each
)
NODE_WRITE = 3 * I32  # committed availability
ROW_READ = 3 * I32 + 3 * I32 + I32 + 1  # driver and executor requests, count, skippable
ROW_WRITE_BASE = I32 + 2 * I32  # driver node, admitted, packed

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def window_bytes(n_nodes: int, rows: float, executors: int) -> float:
    """Bytes one window has to move at least."""
    return n_nodes * (NODE_READ + NODE_WRITE) + rows * (
        ROW_READ + ROW_WRITE_BASE + I32 * executors
    )


def peak(device_kind: str, key: str) -> float:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in {PEAKS}")
    return float(table[device_kind][key])
