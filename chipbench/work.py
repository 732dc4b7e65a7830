"""Bytes and operations of one operator pass, and its least time.

A pass is one product of the operator with a block of `lanes` query
vectors, whatever kernel or fusion does it. It is counted from the
deployment's stated storage alone:

* dense: the n x n float32 matrix, n^2 * 4 bytes;
* sparse: each stored non-zero's 4-byte value and 4-byte column index,
  plus the n * 4-byte diagonal. Zero padding of a blocked layout is not
  counted, so a layout that stores less shows as a gain;

both plus the lanes' float32 (lanes, n) input and output. Operations are
2 * nnz * lanes (nnz = n^2 for dense). The least time is the larger of
bytes over the HBM bandwidth and operations over the peak rate.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown device is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add the chip with its source")
    return table[device_kind]


def pass_bytes(storage: dict, lanes: int) -> int:
    n, nnz = int(storage["n"]), int(storage["nnz"])
    vb = int(storage.get("value_bytes", 4))
    if storage["kind"] == "dense":
        a = nnz * vb
    elif storage["kind"] == "sparse":
        a = nnz * (vb + int(storage.get("index_bytes", 4))) + n * vb
    else:
        raise ValueError(f"unknown storage kind {storage['kind']!r}")
    return a + 2 * lanes * n * 4


def pass_flops(storage: dict, lanes: int) -> int:
    return 2 * int(storage["nnz"]) * lanes


def least_time(storage: dict, lanes: int, peaks: dict) -> tuple[float, str]:
    """(seconds, bound) of one pass: bound is "hbm" or "flops"."""
    t_bytes = pass_bytes(storage, lanes) / float(peaks["hbm_bytes_per_s"])
    t_flops = pass_flops(storage, lanes) / float(peaks["flops_per_s"])
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
