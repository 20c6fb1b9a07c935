"""Peaks of the chip, and the least time a count of work needs there.

The peaks table (``bench/peaks.json``) is keyed by ``device_kind``; a
device that is not in it is an error, never a default. The operations and
bytes of a model's work follow from its block's equations, and are counted
by its architecture's ``Shape`` (``bench/archs/<arch>.py``).
"""
from __future__ import annotations

import json
import os
from typing import Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to "
                       f"bench/peaks.json with its source")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """The larger of compute time and memory time at the chip's peaks, and
    which of the two bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
