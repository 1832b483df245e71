"""The benchmark's own statement client and closed-loop driver.

A copy of the loop in the program's `run_statement` over plain urllib:
POST /v1/statement, then follow nextUri to the last row. Nothing of the
program is imported. Every statement leaves a record: its template and
parameters, POST and last-row times on the host clock, the HTTP round
trips, and the rows or the error.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
import urllib.request
from typing import Callable, Iterator, List, Tuple

HTTP_TIMEOUT_S = 120.0


def run_statement(base: str, sql: str, deadline: float,
                  annotate: Callable = None) -> Tuple[list, int]:
    """(rows, round trips). Raises on an error payload, on a statement
    still running at `deadline` (time.perf_counter), on HTTP failure."""
    annotate = annotate or (lambda name: contextlib.nullcontext())
    req = urllib.request.Request(
        base + "/v1/statement", data=sql.encode(),
        headers={"Content-Type": "text/plain"}, method="POST")
    with annotate("bench_post"):
        with urllib.request.urlopen(req, timeout=HTTP_TIMEOUT_S) as resp:
            payload = json.load(resp)
    rows: list = []
    trips = 1
    while True:
        if "error" in payload:
            raise RuntimeError(str(payload["error"].get("message"))[:500])
        rows.extend(payload.get("data", []))
        nxt = payload.get("nextUri")
        if not nxt:
            return rows, trips
        if time.perf_counter() > deadline:
            raise TimeoutError(f"statement {payload.get('id')} not done "
                               "a minute after the window closed")
        with annotate("bench_poll"):
            with urllib.request.urlopen(nxt, timeout=HTTP_TIMEOUT_S) as r:
                payload = json.load(r)
        trips += 1


def timed_statement(base: str, name: str, params: dict, sql: str,
                    deadline: float, client: int = 0,
                    annotate: Callable = None) -> dict:
    rec = {"client": client, "template": name, "params": params,
           "t_post": time.perf_counter(), "ok": False, "rows": None,
           "trips": 0, "error": None}
    try:
        rec["rows"], rec["trips"] = run_statement(base, sql, deadline,
                                                  annotate)
        rec["ok"] = True
    except Exception as e:  # the boundary: a failed statement is a count
        rec["error"] = f"{type(e).__name__}: {e}"[:500]
    rec["t_done"] = time.perf_counter()
    return rec


def closed_loop(base: str, streams: List[Iterator], seconds: float,
                grace_s: float = 60.0, annotate: Callable = None,
                min_statements: int = 0) -> Tuple[float, List[dict]]:
    """One thread per client; each sends its next statement as soon as the
    last came back, and issues none after `seconds` (but at least
    `min_statements`). Returns (t_open, records); the window closes when
    the last thread has ended."""
    records: List[List[dict]] = [[] for _ in streams]
    t_open = time.perf_counter()
    deadline = t_open + seconds + grace_s

    def loop(i: int, stream: Iterator) -> None:
        for name, params, sql in stream:
            if (time.perf_counter() - t_open >= seconds
                    and len(records[i]) >= min_statements):
                return
            records[i].append(timed_statement(
                base, name, params, sql, deadline, i, annotate))

    threads = [threading.Thread(target=loop, args=(i, s), daemon=True,
                                name=f"bench-client-{i}")
               for i, s in enumerate(streams)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return t_open, [r for per in records for r in per]
