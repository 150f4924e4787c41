"""Peaks of the device and the least work a kernel call must do.

A roofline share is the least time the chip could take for a call --
the larger of operations / peak operations-per-second and bytes / peak
bytes-per-second -- divided by the time the trace shows for it. The
functions here count what the ALGORITHM must move for the call's
shapes, no more: counting what an implementation happens to move would
let a share pass 100 %.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = json.loads((Path(__file__).parent / "peaks.json").read_text())


def peaks(device_kind: str) -> dict:
    """The table's row for a device; an unknown device is an error."""
    if device_kind not in PEAKS or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json: add its row, with a source")
    return PEAKS[device_kind]


def least_seconds(work: dict, device_kind: str) -> tuple[float, str]:
    """-> (least time, which bound) for {"ops": n, "bytes": n}."""
    p = peaks(device_kind)
    t_ops = work.get("ops", 0.0) / p["bf16_flops_per_s"]
    t_mem = work.get("bytes", 0.0) / p["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops > t_mem else (t_mem, "memory")


# ---- work models, one a kernel family, found by name ----------------


def cube_match(queries: float, targets: float) -> dict:
    """Resolve `queries` LocalMessages to `targets` subscribed rows
    through a hashed cube index (`_match_run_csr_kernel` and
    `_pack_csr_kernel` together): each query's 8-byte key and sender and
    replication words are read (16 B), one 16-byte probe-table entry is
    read, each target's 4-byte peer id is read from the index and
    written to the CSR values, and queries + 1 4-byte offsets are
    written. Compares and prefix sums: no bf16 operation is needed."""
    return {"ops": 0.0,
            "bytes": queries * (16 + 16) + targets * (4 + 4)
            + (queries + 1) * 4}


def knn_select(entities: float, k: float, window: float) -> dict:
    """k nearest of `window` candidates for each of `entities` rows
    (`ops/knn_pallas.py::knn_select`): every candidate distance needs 3
    subtractions, 3 multiplications and 2 additions (8 ops); positions
    (12 B) are read once and k 4-byte neighbour ids written a row."""
    return {"ops": entities * window * 8.0,
            "bytes": entities * (12 + 4 * k)}


MODELS = {"cube_match": cube_match, "knn_select": knn_select}
