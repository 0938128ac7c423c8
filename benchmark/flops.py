"""The table of peaks and the roofline, the part of the yardstick's
arithmetic that no architecture owns.  The operations and bytes a model or a
kernel needs, computed from shapes, are ``work/<name>.py``, named by the
configuration's ``"work"`` key.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks.  A device that is not in the table is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as fp:
        table = json.load(fp)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in benchmark/"
            f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def roofline_seconds(ops: float, moved: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which peak bounds it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = moved / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
