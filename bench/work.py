"""The chip's peaks and the roofline: the part of the required-work
arithmetic every architecture shares.

What a model requires (its parameter and K/V bytes, its FLOPs per token,
per prefill span and per decode tick) is counted from its shapes by its
architecture module's ``work(m)`` (``archs/<name>.py``); the readers reach
it as ``run.model``.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks. A device missing from the table is an
    error: a share of an unknown peak means nothing."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline: the larger of compute time and memory time, and which
    of the two bounds it."""
    tc = flops / peak["bf16_flop_s"]
    tm = nbytes / peak["hbm_byte_s"]
    return (tm, "memory") if tm >= tc else (tc, "compute")
