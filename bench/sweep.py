"""Find an open-loop cell's knee once, by a sweep of offered rates on the chip.

    python3 bench/sweep.py --workload ids-v1.mixed-small --seed 7 \\
        --seconds 4 --rates 1000,2000,4000

One process sets the cell up once, then offers each rate for ``--seconds``
with the cell's own mix and prints one JSON line per rate: offered and
completed requests per second, p50, p95, p99 and the largest latency (from the
scheduled arrival), how late the generator ran, and whether every answer
matched the reference.  The knee is the highest rate whose completed rate
keeps up with the offered one with no growing backlog (the last requests'
latency no higher than the first ones') and whose p99 stays under the
cell's limit outside a stall.  The cell then runs at a fixed rate, written into its traffic
file; the benchmark never searches for a rate.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import numpy as np

from run import (MID, build_server, check, fit_models, load_cell,  # noqa
                 make_routes, require_chips, use_compile_cache)
from bench import traffic as tr  # noqa: E402


async def sweep(cell, zoo, lane_pool, routes, models, rates, seconds, seed):
    from repro.runtime import SizeOrDeadlinePolicy
    from repro.serving import ContinuousZooServer

    t = cell["traffic"]
    srv = ContinuousZooServer(zoo, policy=SizeOrDeadlinePolicy(**t["policy"]),
                              n_slots=cell["config"]["server"]["n_slots"],
                              lane_pool=lane_pool)
    await srv.start()
    mids = [MID[r.versions[0].kind] for r in routes]
    out = []
    for rate in rates:
        mix = dict(t, rate_rps=rate)
        n = tr.n_requests(mix, seconds)
        reqs = tr.make_requests(mix, n, seed,
                                [len(r.versions[0].pool) for r in routes])
        feats = [routes[r].versions[0].pool[rows]
                 for r, rows in zip(reqs.route, reqs.rows)]

        async def submit(i):
            r = reqs.route[i]
            return await srv.submit(feats[i], mid=mids[r], vid=routes[r].vid)

        t0, lat, results, late = await tr.open_loop(
            submit, reqs.arrival, n_clients=int(t.get("clients", 8)))
        done = max(r.t_done for r in results if not isinstance(r, Exception))
        raw = {"records": list(enumerate(results)), "installs": [],
               "swap_route": None}
        _, _, wrong, lost = check(routes, reqs, raw,
                                  cell["config"]["profile"]["feature_width"],
                                  cell["config"]["svm_frac_bits"])
        q = len(lat) // 10
        row = {"offered_rps": rate, "requests": n,
               "completed_rps": n / (done - t0),
               "p50_ms": float(np.nanpercentile(lat, 50) * 1e3),
               "p95_ms": float(np.nanpercentile(lat, 95) * 1e3),
               "p99_ms": float(np.nanpercentile(lat, 99) * 1e3),
               "max_ms": float(np.nanmax(lat) * 1e3),
               "first_tenth_p50_ms": float(np.nanmedian(lat[:q]) * 1e3),
               "last_tenth_p50_ms": float(np.nanmedian(lat[-q:]) * 1e3),
               "late_ms": late * 1e3, "wrong": wrong, "lost": lost}
        print(json.dumps(row), flush=True)
        out.append(row)
    await srv.stop()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = load_cell(args.workload)
    if cell["traffic"]["loop"] != "open":
        raise SystemExit("a sweep is for open-loop cells")
    devices = require_chips(cell["chips"])
    use_compile_cache()
    t0 = time.perf_counter()
    models = fit_models(cell["config"], args.seed)
    zoo, lane_pool = build_server(cell["config"], models,
                                  devices[:cell["chips"]])
    routes = make_routes(cell["traffic"], models)
    rates = [float(r) for r in args.rates.split(",")]
    asyncio.run(sweep(cell, zoo, lane_pool, routes, models, rates,
                      args.seconds, args.seed))
    print(f"# sweep took {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
