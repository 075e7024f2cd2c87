"""Peak rates of each accelerator, keyed by JAX's ``device_kind``, and
the HBM bytes each kernel on the daemon's path must move per call.

Source of the v5e numbers: Google Cloud documentation, "TPU v5e"
(system architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s per chip. A device that is not in the table is an error: a
share of an unknown peak means nothing.

The byte counts take the arrays of one call as the trace's HLO text
gives them, each with its memory space: XLA may already have placed a
small operand in the core's VMEM (memory space 1), and a kernel that
reads it there moves none of its bytes over HBM, so only arrays in
memory space 0 count.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12, "hbm_bytes": 16e9},
}

INT32 = 4
HBM = 0


def for_device(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {kind!r}; add them "
                       "to bench/peaks.py with their source")
    return PEAKS[kind]


def _size(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def scan_bytes(arrays) -> int:
    """relscan pass 1 (``_scan_kernel``) reads each term's int32 column
    and the int32 validity whole, and writes the int32 match mask and one
    (8, 128) count tile per grid step: every array once, those in HBM
    over HBM. ``arrays``: (dims, memory space) of operands and outputs."""
    return sum(_size(d) for d, sp in arrays if sp == HBM) * INT32


def probe_bytes(queries: int, bucket_cap: int, tables, outputs) -> int:
    """hashidx ``_probe_kernel``: per query, the aligned (8, bucket_cap)
    tile of each of the row-id and key tables is read; the padded
    candidate and hit rows are written."""
    read = sum(queries * 8 * bucket_cap for _, sp in tables if sp == HBM)
    return (read + sum(_size(d) for d, sp in outputs if sp == HBM)) * INT32
