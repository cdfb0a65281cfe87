"""Traffic: one general generator that reads a mix's parameters, and the two
load drivers.

Every seed gets the same work in another order: the request sizes, the
(pipeline, slot) targets and the open-loop inter-arrival gaps are fixed
multisets drawn from a base stream, and the seed only permutes them and
draws which test rows each request carries.  So runs on different seeds
differ in content, not in how much work they offer.

``open_loop`` is a copy of ``repro.serving.loadgen.open_loop``'s firing and
accounting: each request fires at its scheduled time whether or not earlier
ones were answered, and its latency runs from the scheduled arrival, so a
stall is charged to every request it delays.  ``closed_loop`` keeps K
requests outstanding, one per client, as callers that each wait for a reply
do.
"""
from __future__ import annotations

import asyncio
import dataclasses

import numpy as np

_BASE_STREAM = 0x5EED


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator for ``stream`` of run ``seed`` (any size of
    whole number)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), *stream]))


def _targets_multiset(mix: list[dict], n: int) -> np.ndarray:
    """``n`` target indices (into the flattened (pipeline, vid) list of
    ``routes(mix)``) in the proportions the mix states, by largest
    remainder."""
    weights = np.asarray([w for _, _, w in routes(mix)], np.float64)
    want = weights / weights.sum() * n
    counts = np.floor(want).astype(np.int64)
    short = n - counts.sum()
    counts[np.argsort(-(want - counts), kind="stable")[:short]] += 1
    return np.repeat(np.arange(len(weights)), counts)


def routes(mix: list[dict]) -> list[tuple[str, int, float]]:
    """(pipeline, vid, weight) for every slot the mix addresses.  A group's
    ``share`` is split over its ``vids`` uniformly, or by Zipf rank when it
    gives ``zipf_s`` (the first vid is the most popular)."""
    out = []
    for g in mix:
        vids = list(g["vids"])
        s = g.get("zipf_s")
        w = (np.ones(len(vids)) if s is None
             else 1.0 / np.arange(1, len(vids) + 1) ** float(s))
        w = w / w.sum() * float(g["share"])
        out += [(g["pipeline"], int(v), float(x)) for v, x in zip(vids, w)]
    return out


@dataclasses.dataclass
class Requests:
    """The requests of one run: target route, packet count and test rows."""

    route: np.ndarray        # int [N] index into routes(mix)
    size: np.ndarray         # int [N]
    rows: list[np.ndarray]   # per request, row indices into its pool
    arrival: np.ndarray | None = None   # open loop: scheduled offset (s)


# The keys a traffic file may carry; any other is refused, so that a
# parameter the generator does not read can never be silently ignored.
_KEYS = {"about", "loop", "arrivals", "burst", "rate_rps", "clients",
         "cycle", "packets_per_request", "mix", "policy", "swaps"}


def _poisson_gaps(base: np.random.Generator, n: int) -> np.ndarray:
    """Exponential gaps of mean 1: a Poisson process of unit rate."""
    return base.exponential(1.0, n)


# Open-loop arrival processes, by the name a traffic file's ``arrivals``
# gives.  Each returns ``n`` gaps between arrival events, of mean 1.
ARRIVALS = {"poisson": _poisson_gaps}


def validate(traffic: dict) -> None:
    """Refuse a traffic file that states what the generator cannot do."""
    unknown = set(traffic) - _KEYS
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}")
    if traffic["loop"] == "open":
        if traffic.get("arrivals") not in ARRIVALS:
            raise ValueError(f"unknown arrival process "
                             f"{traffic.get('arrivals')!r}; known: "
                             f"{sorted(ARRIVALS)}")
        if int(traffic.get("burst", 1)) < 1:
            raise ValueError("a burst holds at least one request")
    elif traffic["loop"] == "closed":
        if {"arrivals", "burst", "rate_rps"} & set(traffic):
            raise ValueError("a closed loop has no arrival process or rate")
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")


def make_requests(traffic: dict, n: int, seed: int,
                  pool_rows: list[int]) -> Requests:
    """``n`` requests for ``traffic``; ``pool_rows[r]`` is the number of
    test rows route ``r`` can draw from.

    Open loop: arrival events follow the process ``arrivals`` names at
    ``rate_rps / burst`` events a second, and each event brings ``burst``
    requests (default 1) at once."""
    validate(traffic)
    base = np.random.default_rng(_BASE_STREAM)
    lo, hi = traffic["packets_per_request"]
    sizes = np.resize(np.arange(lo, hi + 1), n)
    targets = _targets_multiset(traffic["mix"], n)
    rng = rng_for(seed, 1)
    size = sizes[rng.permutation(n)]
    route = targets[rng.permutation(n)]
    rows = [rng.integers(0, pool_rows[r], s) for r, s in zip(route, size)]
    arrival = None
    if traffic["loop"] == "open":
        burst = int(traffic.get("burst", 1))
        events = -(-n // burst)
        gaps = ARRIVALS[traffic["arrivals"]](base, events)
        gaps = gaps[rng.permutation(events)]
        t = np.cumsum(gaps) / gaps.sum() * (n / traffic["rate_rps"])
        arrival = np.repeat(t, burst)[:n]
    return Requests(route, size, rows, arrival)


def n_requests(traffic: dict, seconds: float) -> int:
    """Open loop: the window's scheduled requests.  Closed loop: the cycle
    of distinct requests the clients walk through."""
    if traffic["loop"] == "open":
        return max(1, int(round(traffic["rate_rps"] * seconds)))
    return int(traffic["cycle"])


async def open_loop(submit, arrivals: np.ndarray, *, n_clients: int = 8,
                    grace_s: float = 60.0):
    """Fire ``await submit(i)`` at ``t0 + arrivals[i]``.  Returns ``t0`` (loop
    clock), per-request latency from the scheduled arrival (NaN where it
    failed), the results, and the largest lateness of a fire (s).  A request
    still unanswered ``grace_s`` after the last fire never came: its result
    is a ``TimeoutError``.

    Finished tasks are dropped as they finish, so the generator keeps no
    more objects alive than are in flight."""
    loop = asyncio.get_running_loop()
    n = len(arrivals)
    latency = np.full(n, np.nan)
    results: list = [None] * n
    pending: set[asyncio.Task] = set()
    late = 0.0
    t0 = loop.time()

    async def fire(i):
        try:
            results[i] = await submit(i)
        except Exception as e:      # counted, never hidden
            results[i] = e
            return
        latency[i] = loop.time() - (t0 + arrivals[i])

    async def client(idxs):
        nonlocal late
        for i in idxs:
            delay = t0 + arrivals[i] - loop.time()
            if delay > 0:
                await asyncio.sleep(delay)
            else:
                late = max(late, -delay)
            task = loop.create_task(fire(i))
            pending.add(task)
            task.add_done_callback(pending.discard)

    await asyncio.gather(*[client(range(c, n, n_clients))
                           for c in range(n_clients)])
    if pending:
        _, never = await asyncio.wait(list(pending), timeout=grace_s)
        for task in never:
            task.cancel()
        await asyncio.gather(*never, return_exceptions=True)
    for i, r in enumerate(results):
        if r is None:
            results[i] = TimeoutError("no answer")
    return t0, latency, results, late


async def closed_loop(submit, *, n_clients: int, n_cycle: int,
                      seconds: float, grace_s: float = 60.0):
    """``n_clients`` clients, each with one request outstanding, walk the
    request cycle until ``seconds`` have passed.  Returns ``t0`` and a list
    of ``(request index, result or exception, t_sent, t_answered)``; a
    request still unanswered ``grace_s`` after the window never came."""
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    t_end = t0 + seconds
    nxt = 0
    done: list = []

    async def client():
        nonlocal nxt
        while loop.time() < t_end:
            i = nxt % n_cycle
            nxt += 1
            t = loop.time()
            try:
                r = await asyncio.wait_for(
                    submit(i), max(t_end - t, 0.0) + grace_s)
            except Exception as e:      # counted, never hidden
                r = e
            done.append((i, r, t, loop.time()))

    await asyncio.gather(*[client() for _ in range(n_clients)])
    return t0, done
