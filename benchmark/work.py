"""The work a match dispatch NEEDS, and the chip's peaks.

The count is of the problem, never of the kernel's tiling: to match a batch
of publishes against the resident subscriptions a program has to read every
subscription's coded levels once (subscriptions x levels x id bytes), read
the publishes' coded levels, and write the matched rows' ids. How often the
program in fact re-streams the table per batch (``tools/roofline.py``
reckons 647 MB at B=4096) is what the share of the roofline is there to
show. No multiply-accumulate is needed by the problem (it is comparisons),
so the bound is the memory's.
"""

from __future__ import annotations

from typing import Dict

#: peaks by ``jax.devices()[0].device_kind``. Source: Google Cloud
#: documentation, "TPU v5e" (system architecture): 197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}
ID_BYTES = 2    # a level's word id as the table codes it (16 bits)
ROW_BYTES = 4   # a matched row's index coming back


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}: "
                       "add it to benchmark/work.py with its source")
    return PEAKS[device_kind]


def match_bytes(resident: int, levels: int, publishes: float,
                rows_per_publish: float) -> float:
    """Bytes one dispatch of ``publishes`` publishes needs moved."""
    return (resident * levels * ID_BYTES
            + publishes * levels * ID_BYTES
            + publishes * rows_per_publish * ROW_BYTES)


def match_least_seconds(device_kind: str, resident: int, levels: int,
                        publishes: float, rows_per_publish: float) -> float:
    return match_bytes(resident, levels, publishes,
                       rows_per_publish) / peaks(device_kind)[
                           "hbm_bytes_per_s"]
