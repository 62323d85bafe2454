"""The one traffic generator (PR 24): a mix is a data file it reads.

``benchmark/traffic/<name>.json`` holds

    {"loop": "closed", "clients": 1,
     "mix": [{"query": "q1", "weight": 1}, ...]}

A closed loop: each client sends its next query when the last one's rows
have come back, so a slower system is offered less load; the window's
length, not a rate, bounds the work. ``mix`` is a multiset of queries of
the configuration's suite. The order is drawn from ``--seed`` block by
block: every block holds each query ``weight`` times, so every seed sends
the same set of queries, in another order. With one query in the mix the
seed changes the data alone.

Only what a cell uses is written: one client. An open loop (arrivals at a
fixed rate, latency from when a query was due) or several clients need
code here, which only a ``benchmark`` PR may add (PERF.md, Open
questions 6).
"""

from __future__ import annotations

import json
import os
from typing import Iterator, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if mix.get("loop") != "closed" or mix.get("clients") != 1:
        raise ValueError(
            f"traffic {name!r}: this generator drives a closed loop of one "
            f"client, the file asks for {mix.get('loop')!r} x "
            f"{mix.get('clients')!r}")
    if not mix.get("mix") or any(int(m["weight"]) < 1 for m in mix["mix"]):
        raise ValueError(f"traffic {name!r}: an empty mix or weight")
    return mix


def queries(mix: dict) -> List[str]:
    """The distinct queries of a mix, in the file's order."""
    return list(dict.fromkeys(m["query"] for m in mix["mix"]))


def schedule(mix: dict, seed: int) -> Iterator[str]:
    """Query names without end, block by block, each block shuffled."""
    block = [m["query"] for m in mix["mix"] for _ in range(int(m["weight"]))]
    rng = np.random.default_rng([seed, 0x7A0FF1C])
    while True:
        for i in rng.permutation(len(block)):
            yield block[i]
