"""From a traffic file and ``--seed`` to the requests of one run.

Everything is drawn from ``--seed``: the calls (by the family's
generator, which gives every seed the same number of each shape or
panel and draws the rows), their order, and (open loop) where the
arrival gaps fall.  The warm-up is another stream of the same
distribution, so it cannot be fitted to the window it precedes."""

from __future__ import annotations

import dataclasses
import importlib
import json
import math
import os
import zlib

import numpy as np

from perfbench import oracle
from perfbench.loadgen import schedule

HERE = os.path.dirname(os.path.abspath(__file__))


def load_traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Mix:
    queries: list            # the window's calls (oracle forms)
    texts: list[str]         # their PQL
    order: list[int]         # open loop: query of request i
    due: np.ndarray | None   # open loop: due time of request i
    per_client: list[list[int]] | None  # closed loop


def family(traffic: dict):
    return importlib.import_module("perfbench.querygen." + traffic["family"])


def _draw(seed: int, k: int) -> np.random.Generator:
    return np.random.default_rng([seed, zlib.crc32(b"calls"), k])


def warm_texts(traffic: dict, n_rows: dict, seed: int) -> list[str]:
    """The warm-up stream: draw 1 of the mix's distribution (the
    windows are the even draws), shuffled like a window, so that calls
    of one shape meet in flight as they do there (the server compiles
    a program for each width of a same-shape batch)."""
    rng = _draw(seed, 1)
    calls = family(traffic).generate(traffic["params"], n_rows, rng,
                                     traffic["warmup_requests"])
    return [oracle.pql(calls[i]) for i in rng.permutation(len(calls))]


def build(traffic: dict, n_rows: dict, seed: int, seconds: float,
          rate: float | None = None, clients: int | None = None,
          stream: int = 0) -> Mix:
    """``rate``/``clients`` override the traffic file's (sweeps and
    trials); ``stream`` picks another draw of the same distribution, for
    a second window on a server whose result cache has seen the first."""
    gen = family(traffic).generate
    rng = np.random.default_rng([seed, zlib.crc32(b"traffic")])
    if traffic["loop"] == "open":
        rate = traffic["rate_qps"] if rate is None else rate
        n = max(1, round(rate * seconds))
    else:
        clients = traffic["clients"] if clients is None else clients
        # more calls than the clients can send, whatever their number
        n = math.ceil(seconds * traffic["max_qps"])
    queries = gen(traffic["params"], n_rows, _draw(seed, 2 * stream), n)
    order = [int(i) for i in rng.permutation(n)]
    mix = Mix(queries, [oracle.pql(q) for q in queries], order, None, None)
    if traffic["loop"] == "open":
        mix.due = schedule(rate, seconds, rng)
    else:
        mix.per_client = [order[i::clients] for i in range(clients)]
    return mix
