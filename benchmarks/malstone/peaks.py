"""Chip peaks and the bytes a MalStone reducer cannot do without.

The peak table is keyed by ``device_kind`` as JAX reports it. A kind that is
not in the table is an error: a roofline share against a guessed peak means
nothing.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 819 GB/s HBM, 197 TFLOP/s bf16.
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}

BIN_RMW_BYTES = 8          # read and write back one int32 bin per record
COLUMN_INPUT_BYTES = 12    # site_id, timestamp and mark, 4 bytes each
PACKED_INPUT_BYTES = 4     # one packed (site, week, mark, valid) word


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown kind."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def reducer_input_bytes(middleware: str) -> int:
    """Bytes per record the site x week reducer must read. The MapReduce
    reducer reads the words the exchange delivered; every other middleware
    reduces the record columns in place."""
    return PACKED_INPUT_BYTES if middleware == "mapreduce" else \
        COLUMN_INPUT_BYTES


def reducer_min_bytes(records: int, middleware: str) -> int:
    """The least HBM traffic of histogramming ``records`` records: their
    input read once plus one read-modify-write of an int32 bin each. It
    counts records, not the buffer a program passes, so padding or a
    doubled receive buffer shows as time above this bound."""
    return records * (reducer_input_bytes(middleware) + BIN_RMW_BYTES)


def roofline_share(min_bytes: float, seconds: float,
                   hbm_bytes_per_s: float) -> float:
    """Per cent of the bandwidth roofline reached: the least time the bytes
    need over the time taken."""
    if seconds <= 0:
        raise ValueError(f"time must be positive, got {seconds!r}")
    return 100.0 * (min_bytes / hbm_bytes_per_s) / seconds
