"""The traffic generator and the two load drivers."""
from __future__ import annotations

import asyncio
import json
import time
from pathlib import Path

import numpy as np
import pytest

from bench import traffic as tr

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(p.stem for p in MIXES.glob("*.json")))
def test_generator_is_deterministic_in_the_seed(name):
    mix = _mix(name)
    n = tr.n_requests(mix, 2.0)
    pools = [500] * len(tr.routes(mix["mix"]))
    a = tr.make_requests(mix, n, 2**31 + 11, pools)
    b = tr.make_requests(mix, n, 2**31 + 11, pools)
    c = tr.make_requests(mix, n, 2**31 + 12, pools)
    assert np.array_equal(a.route, b.route) and np.array_equal(a.size, b.size)
    assert all(np.array_equal(x, y) for x, y in zip(a.rows, b.rows))
    # another seed: the same work in another order
    assert not np.array_equal(a.size, c.size)
    assert np.array_equal(np.sort(a.size), np.sort(c.size))
    assert np.array_equal(np.sort(a.route), np.sort(c.route))
    lo, hi = mix["packets_per_request"]
    assert a.size.min() >= lo and a.size.max() <= hi
    if mix["loop"] == "open":
        assert np.array_equal(a.arrival, b.arrival)
        assert np.allclose(np.sort(np.diff(a.arrival, prepend=0)),
                           np.sort(np.diff(c.arrival, prepend=0)))
        assert a.arrival[-1] == pytest.approx(n / mix["rate_rps"])


def test_mix_shares_and_zipf_ranks():
    routes = tr.routes(_mix("zipf-large")["mix"])
    w = {(p, v): x for p, v, x in routes}
    assert sum(w.values()) == pytest.approx(1.0)
    assert sum(x for (p, _), x in w.items() if p == "tree") \
        == pytest.approx(0.8)
    tree = [w[("tree", v)] for v in range(8)]
    assert tree == sorted(tree, reverse=True)
    assert tree[0] / tree[1] == pytest.approx(2 ** 1.1)
    assert w[("svm", 0)] == pytest.approx(w[("svm", 1)])


def test_open_loop_charges_latency_from_the_scheduled_arrival():
    """A stall that holds the loop delays every later fire; each delayed
    request is charged from when it was due, not from when it fired."""
    arrivals = np.arange(10) * 0.01

    async def main():
        async def submit(i):
            if i == 0:
                time.sleep(0.2)      # blocks the loop: later fires run late
            return i

        return await tr.open_loop(submit, arrivals, n_clients=2)

    _, latency, results, late = asyncio.run(main())
    assert results == list(range(10))
    assert late >= 0.1
    for i in range(1, 10):
        assert latency[i] >= 0.2 - arrivals[i] - 0.005


def test_open_loop_counts_failures():
    async def main():
        async def submit(i):
            if i == 3:
                raise RuntimeError("refused")
            return i

        return await tr.open_loop(submit, np.arange(5) * 0.001)

    _, latency, results, _ = asyncio.run(main())
    assert isinstance(results[3], RuntimeError) and np.isnan(latency[3])
    assert np.isfinite(np.delete(latency, 3)).all()


def test_closed_loop_keeps_k_requests_outstanding():
    K = 5
    state = {"now": 0, "peak": 0, "seen": []}

    async def main():
        async def submit(i):
            state["now"] += 1
            state["peak"] = max(state["peak"], state["now"])
            state["seen"].append(state["now"])
            await asyncio.sleep(0.01)
            state["now"] -= 1
            return i

        return await tr.closed_loop(submit, n_clients=K, n_cycle=7,
                                    seconds=0.3)

    _, done = asyncio.run(main())
    assert state["peak"] == K
    # after the first round every submit finds the other K-1 outstanding
    assert min(state["seen"][K:]) == K
    assert 20 * K <= len(done) <= 40 * K
    assert [i for i, *_ in sorted(done, key=lambda d: d[2])][:7] \
        == list(range(7))


def test_an_answer_that_never_comes_is_counted():
    async def main():
        async def submit(i):
            if i == 1:
                await asyncio.sleep(10)
            return i

        return await tr.open_loop(submit, np.arange(3) * 0.001, grace_s=0.1)

    _, latency, results, _ = asyncio.run(main())
    assert isinstance(results[1], TimeoutError) and np.isnan(latency[1])
    assert results[0] == 0 and results[2] == 2


def test_unknown_arrival_process_is_refused():
    mix = dict(_mix("mixed-small"), arrivals="lognormal")
    with pytest.raises(ValueError, match="arrival process"):
        tr.make_requests(mix, 10, 1, [50, 50])
    with pytest.raises(ValueError, match="arrival process"):
        tr.validate({k: v for k, v in _mix("mixed-small").items()
                     if k != "arrivals"})


@pytest.mark.parametrize("extra", [{"burts": 8}, {"arrivals": "poisson"}])
def test_a_key_the_generator_does_not_read_is_refused(extra):
    with pytest.raises(ValueError):
        tr.validate(dict(_mix("zipf-large"), **extra))


def test_bursts_arrive_together_at_the_stated_rate():
    mix = dict(_mix("mixed-small"), burst=8)
    n = tr.n_requests(mix, 2.0)
    a = tr.make_requests(mix, n, 2**31 + 3, [500, 500])
    events = np.unique(a.arrival)
    assert len(events) == -(-n // 8)
    assert np.bincount(np.searchsorted(events, a.arrival)).max() == 8
    assert a.arrival[-1] == pytest.approx(n / mix["rate_rps"])
