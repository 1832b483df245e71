"""The table of peaks and the bytes a statement must move: the kernels'
roofline read from the work, not from the implementation."""

from __future__ import annotations

from typing import Dict

from qgen import load_json


def peaks(device_kind: str) -> dict:
    """The chip's published peaks. A device the table does not hold is an
    error, never a default."""
    table = load_json("peaks.json")
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmarks/peaks.json")
    return table[device_kind]


def statement_bytes(query: dict, rows: Dict[str, int]) -> int:
    """Least bytes to read each column the template references once."""
    widths = load_json("column_bytes.json")
    return sum(rows[table] * widths[table][col]
               for table, cols in query["reads"].items() for col in cols)


def hbm_floor_s(n_bytes: float, device_kind: str, chips: int = 1) -> float:
    """Least time `chips` chips could take to read `n_bytes` from HBM."""
    return n_bytes / (peaks(device_kind)["hbm_bytes_per_s"] * chips)
