"""The one traffic generator: a traffic file (clients, cycle of templates)
and a query file per template (SQL with placeholders, substitution rules)
give, for a seed, every client's endless sequence of statements.

Statement k of client c has the same text for the same seed whatever the
speed of the system, and every seed has the same mix of templates (the
clients' starting offsets into the cycle are a seeded permutation), so a
seed reorders the work and does not change it. Pure Python.
"""

from __future__ import annotations

import datetime
import importlib.util
import json
import os
import random
from typing import Dict, Iterator, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
_EPOCH = datetime.date(1970, 1, 1)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_py(*parts: str):
    """A module from a file of the benchmark, found by its name."""
    path = os.path.join(HERE, *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(parts).replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_traffic(name: str) -> dict:
    return load_json("traffic", name + ".json")


def load_query(name: str) -> dict:
    return load_json("queries", name + ".json")


def days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def draw(rule: dict, rng: random.Random):
    """One substitution parameter by its rule (TPC-H clause 2.4.x.3)."""
    kind = rule["rule"]
    if kind == "fixed":
        return rule["value"]
    if kind == "int":
        return rng.randint(rule["lo"], rule["hi"])
    if kind == "decimal":  # lo..hi in steps, kept as text: SQL decimals
        n = round((rule["hi"] - rule["lo"]) / rule["step"])
        return f'{rule["lo"] + rng.randint(0, n) * rule["step"]:.2f}'
    if kind == "date_jan1":
        return f'{rng.randint(rule["lo"], rule["hi"])}-01-01'
    if kind == "date":
        lo = datetime.date.fromisoformat(rule["lo"])
        span = (datetime.date.fromisoformat(rule["hi"]) - lo).days
        return (lo + datetime.timedelta(rng.randint(0, span))).isoformat()
    if kind == "choice":
        return rng.choice(rule["values"])
    raise ValueError(f"unknown substitution rule {kind!r}")


def statement(query: dict, rng: random.Random) -> Tuple[dict, str]:
    params = {k: draw(rule, rng) for k, rule in sorted(
        query["params"].items())}
    return params, query["sql"].format(**params)


def offsets(traffic: dict, seed: int) -> List[int]:
    """Where in the cycle each client starts: client i of n starts at
    slot (i * len(cycle)) // n, and the seed only permutes which client
    gets which slot."""
    n, m = traffic["clients"], len(traffic["cycle"])
    slots = [(i * m) // n for i in range(n)]
    random.Random(f"{seed}:offsets").shuffle(slots)
    return slots


def client_stream(traffic: dict, queries: Dict[str, dict], seed: int,
                  client: int) -> Iterator[Tuple[str, dict, str]]:
    """(template, params, sql) for statement 0, 1, 2... of one client."""
    cycle = traffic["cycle"]
    start = offsets(traffic, seed)[client]
    k = 0
    while True:
        name = cycle[(start + k) % len(cycle)]
        rng = random.Random(f"{seed}:window:{client}:{k}")
        params, sql = statement(queries[name], rng)
        yield name, params, sql
        k += 1
