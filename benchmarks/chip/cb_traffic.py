"""The general traffic generator: a traffic file (``traffic/<name>.json``)
is data, and this module turns it and a seed into the run's requests.

A traffic file holds:

- ``loop``: ``"closed"`` (``streams`` clients, each sending its next
  request when the previous one has completed) or ``"open"`` (Poisson
  arrivals at ``rate_per_s``, sent on schedule whatever the backlog);
- ``schedule_seed``: fixes the order of the requests' sizes and of the
  open loop's arrival gaps, the same on every run seed: a run's seed draws
  the data and the ``uniform_int`` parameters, not the schedule.  (With the
  order drawn from the run seed, the ERA5 cell's p95 latency moved by a
  factor of 2.7 from seed to seed, against 13 % between two runs of one
  seed: the order of large requests, not the program, set the tail);
- ``params``: per-request parameters, drawn in order.  ``balanced_int``
  deals every value of an inclusive range equally often over the request
  list, in the schedule's order; ``uniform_int`` draws each request's
  value from the run seed and may name parameters drawn before it;
- ``query``: the request as data (sources, filter, projections, group keys,
  which may be empty for one global group, aggregates, client-side
  ordering).  Literal sub-expressions (``["date", "1998-12-01"]``,
  ``["date_ymd", year, month, day]``, ``["param", "DELTA"]``,
  ``["config", key]`` and arithmetic over them) are folded to numbers per
  request, so a request's expression tree holds only ``["col", name]``,
  numbers and operators;
- ``check``: the sample of answers compared with the plain reference and
  the limits of each compared number.

An open-loop window of ``s`` seconds sends ``n = round(rate_per_s * s)``
requests.  Their gaps are the exponential distribution's quantiles at
``(i + 0.5) / n``, dealt into the window's two halves so that each half
carries ``n / 2`` arrivals (a later half with more arrivals would look like
a growing backlog), scaled to fill each half and put in the schedule's
order: the arrivals are Poisson-like, and every seed sends the same
requests' sizes at the same times.
"""

from __future__ import annotations

import numpy as np

ARITH = {"add": lambda a, b: a + b, "sub": lambda a, b: a - b, "mul": lambda a, b: a * b}


def date_days(iso: str) -> int:
    return int(np.datetime64(iso, "D").astype(np.int64))


def fold(tree, params: dict, config: dict):
    """Fold the literal parts of an expression tree into numbers."""
    if isinstance(tree, (int, float)):
        return tree
    op = tree[0]
    if op == "col":
        return tree
    if op == "date":
        return date_days(tree[1])
    if op == "param":
        return params[tree[1]]
    if op == "config":
        return config[tree[1]]
    args = [fold(a, params, config) for a in tree[1:]]
    if op == "date_ymd":
        y, m, d = (int(a) for a in args)
        return date_days(f"{y:04d}-{m:02d}-{d:02d}")
    if op in ARITH and all(isinstance(a, (int, float)) for a in args):
        return ARITH[op](*args)
    return [op, *args]


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *salt])


def draw_params(specs: list, n: int, seed: int, config: dict, schedule_seed: int) -> list:
    """``n`` parameter dicts, drawn in the order of ``specs``."""
    rng, schedule = _rng(seed, 0x7A), _rng(schedule_seed, 0x7B)
    out = [dict() for _ in range(n)]
    for spec in specs:
        name, draw = spec["name"], spec["draw"]
        if draw == "balanced_int":
            lo, hi = (int(fold(v, {}, config)) for v in spec["range"])
            vals = np.resize(np.arange(lo, hi + 1), n)
            for p, v in zip(out, schedule.permutation(vals)):
                p[name] = int(v)
        elif draw == "uniform_int":
            u = rng.random(n)
            for p, x in zip(out, u):
                lo, hi = (int(fold(v, p, config)) for v in spec["range"])
                p[name] = lo + min(int(x * (hi - lo + 1)), hi - lo)
        else:
            raise ValueError(f"unknown draw {draw!r} for parameter {name!r}")
    return out


def source_tables(sources, params: dict, config: dict) -> list:
    """The table names one request reads."""
    if isinstance(sources, list):
        return list(sources)
    start = int(fold(sources["start"], params, config))
    count = int(fold(sources["count"], params, config))
    return [sources["pattern"].format(start + j) for j in range(count)]


def build_requests(traffic: dict, config: dict, seed: int, n: int) -> list:
    """``n`` requests: dicts with ``id``, ``params``, ``sources`` and the
    folded ``filter`` (or None)."""
    query = traffic["query"]
    out = []
    params = draw_params(traffic.get("params", []), n, seed, config, int(traffic["schedule_seed"]))
    for i, p in enumerate(params):
        flt = query.get("filter")
        out.append(
            {
                "id": i,
                "params": p,
                "sources": source_tables(query["sources"], p, config),
                "filter": None if flt is None else fold(flt, p, config),
            }
        )
    return out


def request_count(traffic: dict, seconds: float) -> int:
    """How many requests the run draws: for a closed loop more than a window
    can use, for an open loop those the window sends."""
    if traffic["loop"] == "closed":
        return int(traffic["streams"]) * int(traffic["requests_per_stream"])
    return max(1, round(float(traffic["rate_per_s"]) * seconds))


def arrival_offsets(n: int, schedule_seed: int, seconds: float) -> np.ndarray:
    """Open loop: each of ``n`` requests' due time, in seconds from the
    window's start, all inside ``[0, seconds)``; the first ``n // 2`` fall
    in the window's first half, the rest in its second."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    if n == 1:
        return np.zeros(1)
    # deal the sorted gaps A B B A A B B A ...: each half gets its share of every size
    first = np.asarray([True, False, False, True])[np.arange(n) % 4]
    first[np.flatnonzero(first)[n // 2 :]] = False  # odd n: the spare gap goes to the second half
    rng = _rng(schedule_seed, 0xA7)
    halves = [rng.permutation(part * (0.5 * seconds / part.sum())) for part in (gaps[first], gaps[~first])]
    return np.concatenate([[0.0], np.cumsum(np.concatenate(halves))[:-1]])


def streams(traffic: dict, requests: list) -> list:
    """Closed loop: request ``i`` goes to stream ``i mod streams``."""
    k = int(traffic["streams"])
    return [requests[s::k] for s in range(k)]
