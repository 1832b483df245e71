"""Arithmetic of the end-to-end metrics: window, rate, percentiles.

Pure Python, no JAX and nothing of the program: the yardstick a later PR
cannot move. A window opens when the clients start, the clients stop
*issuing* at `--seconds`, and it closes when the last statement in flight
has come back; its true length is the divisor of every rate.
"""

from __future__ import annotations

import math
from typing import Iterable, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks (numpy's default), over ALL the values given."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q} outside 0..100")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def window_length(t_open: float, done_times: Iterable[float],
                  seconds: float) -> float:
    """True length of the window: to the completion of the last statement,
    and never under the time the clients were allowed to issue."""
    last = max(done_times, default=t_open + seconds)
    return max(last - t_open, seconds)


def per_hour(count: int, window_s: float) -> float:
    """TPC-H's throughput form: statements an hour over the whole window,
    so a stalled statement lengthens the divisor and moves the rate."""
    if window_s <= 0:
        raise ValueError("window has no length")
    return 3600.0 * count / window_s


def walls(records: Iterable[dict]) -> List[float]:
    """POST -> last row, of every statement that came back with rows."""
    return [r["t_done"] - r["t_post"] for r in records if r["ok"]]
