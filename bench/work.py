"""The work a request asks for, and the chip's peaks: the roofline's
yardstick, counted from the requests and never from a program.

Operations per DP cell follow each kernel's recurrence, pointer
selection included, as 32-bit vector operations (add, max, compare,
select, shift, or).  Affine gap (Gotoh), three states:

    I   = max(H_left + open, I_left + extend)       2 add, 1 max
    Ibit = I_left + extend > H_left + open          1 compare
    D   = max(H_up + open, D_up + extend)           2 add, 1 max
    Dbit = D_up + extend > H_up + open              1 compare
    M   = H_diag + (q == r ? match : mismatch)      1 compare, 1 select, 1 add
    H, src = best of M, D, I                        2 compare, 2 select, 2 max
    ptr = src | Ibit << 2 | Dbit << 3               2 shift, 2 or
                                                    = 21 (global_affine)
    local: src = H <= 0 ? END : src; H = max(H, 0)  1 compare, 1 select, 1 max
                                                    = 24 (local_affine)

Only the cells a request needs count, ``len(q) * len(r)``: padding and
the cells past a request's end are the implementation's choice.  The
traceback is ``O(len(q) + len(r))`` per request and is left out.

Bytes are what must cross HBM at the least: the two sequences in (one
byte a base) and two int32 lengths, and out the score, the end cell and
the path (at least ``max(len(q), len(r))`` one-byte moves and an int32
count).  The traceback store is one implementation's choice and is not
counted.
"""
from __future__ import annotations

import json
from pathlib import Path

OPS_PER_CELL = {
    "global_affine": 21,
    "local_affine": 24,
}

# the peaks table, keyed by ``jax.devices()[0].device_kind``, each peak
# with its source
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def ops_per_cell(kernel: str) -> int:
    try:
        return OPS_PER_CELL[kernel]
    except KeyError:
        raise KeyError(f"no operation count for kernel {kernel!r}; add one "
                       f"to bench/work.py") from None


def peaks(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    try:
        p = table[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device {device_kind!r}") from None
    if p["int32_ops_per_s"] is None:
        raise KeyError(f"the 32-bit vector-op peak of {device_kind!r} has "
                       f"not been measured")
    return p


def request_ops(kernel: str, q_len: int, r_len: int) -> int:
    return ops_per_cell(kernel) * int(q_len) * int(r_len)


def request_bytes(q_len: int, r_len: int) -> int:
    return int(q_len) + int(r_len) + 8 + 12 + max(int(q_len), int(r_len)) + 4


def roofline(kernel: str, lengths, seconds: float, device_kind: str) -> dict:
    """Share of the roofline that ``lengths`` (``(q, r)`` pairs) reach
    in ``seconds`` of device time, and which bound applies."""
    p = peaks(device_kind)
    ops = sum(request_ops(kernel, q, r) for q, r in lengths)
    nbytes = sum(request_bytes(q, r) for q, r in lengths)
    t_ops = ops / p["int32_ops_per_s"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    bound = "ops" if t_ops >= t_bytes else "bytes"
    return {"share": max(t_ops, t_bytes) / seconds, "bound": bound,
            "ops": ops, "bytes": nbytes}
