"""The yardstick's peaks and the Algorithm-1 work count (``peaks.json``)."""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def load(path: str = PEAKS_FILE) -> dict:
    with open(path) as f:
        return json.load(f)


def peak(device_kind: str, table: dict | None = None) -> dict:
    """Peak FLOP/s and bytes/s of one chip; an unknown device is an error."""
    devices = (table or load())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(devices)}")
    return devices[device_kind]


def solve_work(rows: int, table: dict | None = None) -> tuple:
    """FLOPs and bytes of ``rows`` Algorithm-1 solves (tasks x classes)."""
    row = (table or load())["algorithm1_row"]
    return rows * row["flops"], rows * row["bytes"]


def least_seconds(rows: int, device_kind: str,
                  table: dict | None = None) -> tuple:
    """The least time the chip could take for ``rows`` solves, and which
    bound sets it (``"flops"`` or ``"bytes"``)."""
    table = table or load()
    pk = peak(device_kind, table)
    flops, nbytes = solve_work(rows, table)
    t_f, t_b = flops / pk["flops_per_s"], nbytes / pk["bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
