"""Run one benchmark cell on the chip and print its result as the last line.

    python3 bench/run.py --workload <config>.<traffic> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); its metrics are the readers
``bench/metrics/<metric>.py`` that ``BENCHMARK.json`` lists for it.  The run
fits the deployment's models from the seed, installs them through the
program's ``ZooServer``, warms the serving front's own bucket ladder, then
drives ``ContinuousZooServer.submit`` for ``--seconds`` and checks every
answered packet against the plain reference (``bench/reference.py``).

It needs a TPU: on any other platform, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import recipe, reference, workcount  # noqa: E402
from bench import trace as tracing  # noqa: E402
from bench import traffic as tr  # noqa: E402
from bench.peaks import peaks  # noqa: E402

# ACORN header MIDs (paper Appendix A): the pipeline a packet selects.
MID = {"dt": 0, "rf": 1, "svm": 2}
TRACE_DIR = ROOT / ".bench_trace"
# The fused classify kernel as the chip's trace names it.
KERNEL_NAMES = ("classify_fused",)


def log(msg: str) -> None:
    print(f"[bench] {time.monotonic() - T_START:8.3f} s  {msg}", flush=True)


# ------------------------------------------------------------------ cells
def load_cell(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic and the metrics it reports."""
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    tr.validate(traffic)
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return {
        "name": name, "chips": int(w["chips"]),
        "config": json.loads((ROOT / cfg["file"]).read_text()),
        "traffic": traffic,
        "end_to_end": [m["name"] for m in e2e],
        "per_layer": [m["name"] for m in layer],
    }


def reader(metric: str):
    """The reader ``bench/metrics/<metric>.py``'s ``read``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------- deployment
@dataclasses.dataclass
class Model:
    name: str
    kind: str
    pipeline: str
    vid: int
    standby: bool
    model: object
    desc: dict
    fp: dict
    cost: dict
    pool: np.ndarray          # test rows, quantized, selected columns


def fit_models(config: dict, seed: int) -> list[Model]:
    prepared: dict[str, recipe.Prepared] = {}
    out = []
    for m in config["models"]:
        ds = m["dataset"]
        if ds not in prepared:
            prepared[ds] = recipe.prepare(ds, config)
        prep = prepared[ds]
        fit_seed = int(np.random.SeedSequence(
            [int(seed) % (1 << 64), int(m["fit_stream"])]).generate_state(1)[0])
        model = recipe.fit(m["kind"], prep, config["hyper"][m["kind"]],
                           fit_seed)
        desc = reference.describe(model)
        fp = reference.fingerprint(desc)
        out.append(Model(
            m["name"], m["kind"], m["pipeline"], int(m["vid"]),
            bool(m.get("standby", False)), model, desc, fp,
            workcount.model_cost(fp, prep.Xte.shape[1], config["profile"]),
            prep.Xte))
    return out


def build_server(config: dict, models: list[Model]):
    """The program under test: a ``ZooServer`` with the configuration's
    models installed."""
    from repro.core.plane import PlaneProfile
    from repro.serving import ZooServer

    if config["executor"]["kind"] != "single":
        raise ValueError(f"unknown executor kind "
                         f"{config['executor']['kind']!r}")
    zoo = ZooServer(PlaneProfile(**config["profile"]))
    for m in models:
        if not m.standby:
            zoo.install(m.model, vid=m.vid, tag=m.name)
    return zoo


# ---------------------------------------------------------------- traffic
@dataclasses.dataclass
class Route:
    pipeline: str
    vid: int
    versions: list[Model]     # installed first; a swap alternates them


def make_routes(traffic: dict, models: list[Model]) -> list[Route]:
    out = []
    for pipeline, vid, _ in tr.routes(traffic["mix"]):
        ms = [m for m in models if m.pipeline == pipeline and m.vid == vid]
        ms.sort(key=lambda m: m.standby)
        if not ms:
            raise ValueError(f"traffic addresses {pipeline} slot {vid}, "
                             "which the configuration leaves empty")
        out.append(Route(pipeline, vid, ms))
    return out


# ----------------------------------------------------------------- window
Answer = collections.namedtuple("Answer",
                                "rslt t_submit t_dispatch t_done")


class GcPauses:
    """Python's collections inside the window: count and longest pause."""

    def __init__(self):
        self.armed = False
        self.pauses: dict[int, list[float]] = {}
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.armed:
            self.pauses.setdefault(info["generation"], []).append(
                time.perf_counter() - self._t)

    def close(self):
        gc.callbacks.remove(self._on)

    def __str__(self):
        return ", ".join(f"gen{g}: {len(p)} (longest {max(p) * 1e3:.1f} ms)"
                         for g, p in sorted(self.pauses.items())) or "none"


class CompileCounter:
    """Counts compilations (and compile-cache loads) while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event in self.EVENTS:
            self.count += 1


async def _swapper(srv, zoo, route: Route, every_s: float, t0: float,
                   t_end: float, installs: list):
    """Live installs into the route's slot, alternating its versions."""
    import jax

    loop = asyncio.get_running_loop()
    k = 1
    while t0 + k * every_s < t_end:
        await asyncio.sleep(max(0.0, t0 + k * every_s - loop.time()))
        version = k % len(route.versions)
        m = route.versions[version]
        t_hold = loop.time()
        await srv.drain()
        t_i = time.perf_counter()
        srv.install(m.model, vid=route.vid, tag=m.name)
        jax.block_until_ready(zoo.packed)
        install_s = time.perf_counter() - t_i
        srv.release()
        installs.append({"t_hold": t_hold, "t_release": loop.time(),
                         "install_s": install_s, "version": version})
        k += 1


async def serve(cell: dict, zoo, routes: list[Route],
                reqs: tr.Requests, feats: list, seconds: float,
                trace: bool, devices, counter: CompileCounter) -> dict:
    """Set up the serving front, run the window, stop.  Returns the raw
    records of the window."""
    import jax
    from repro.runtime import SizeOrDeadlinePolicy
    from repro.serving import ContinuousZooServer

    t = cell["traffic"]
    policy = SizeOrDeadlinePolicy(**t["policy"])
    srv = ContinuousZooServer(zoo, policy=policy,
                              n_slots=cell["config"]["server"]["n_slots"])
    t_l = time.perf_counter()
    await srv.start()
    ladder_s = time.perf_counter() - t_l
    log(f"ladder: {len(srv.warmed_buckets)} buckets "
        f"{list(srv.warmed_buckets)} in {ladder_s:.3f} s")
    swap = t.get("swaps")
    swap_route = None
    if swap:
        (swap_route,) = [r for r in routes if r.pipeline == swap["pipeline"]
                         and r.vid == swap["vid"]]
        if len(swap_route.versions) < 2:
            raise ValueError("a swap needs a standby version in the slot")
        t_w = time.perf_counter()
        m = swap_route.versions[0]
        srv.install(m.model, vid=swap_route.vid, tag=m.name)
        jax.block_until_ready(zoo.packed)
        log(f"warm-up install into {swap['pipeline']} slot {swap['vid']} "
            f"in {time.perf_counter() - t_w:.3f} s")
    mids = [MID[r.versions[0].kind] for r in routes]
    # what set-up left behind is never collected inside the window
    gc.collect()
    gc.freeze()

    async def submit(i):
        r = reqs.route[i]
        res = await srv.submit(feats[i], mid=mids[r], vid=routes[r].vid)
        # keep the answer, not the program's result object
        return Answer(res.rslt, res.t_submit, res.t_dispatch, res.t_done)

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    loop = asyncio.get_running_loop()
    installs: list = []
    counter.armed = True
    pauses = GcPauses()
    pauses.armed = True
    setup_s = time.monotonic() - T_START
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        if t["loop"] == "open":
            t0, latency, results, late = await tr.open_loop(
                submit, reqs.arrival, n_clients=int(t.get("clients", 8)))
            records = [(i, r) for i, r in enumerate(results)]
            t_end = t0 + seconds
        else:
            swapper = None
            t0 = loop.time()
            if swap:
                swapper = loop.create_task(_swapper(
                    srv, zoo, swap_route, float(swap["every_s"]), t0,
                    t0 + seconds, installs))
            t0, done = await tr.closed_loop(
                submit, n_clients=int(t["clients"]),
                n_cycle=len(reqs.size), seconds=seconds)
            if swapper is not None:
                await swapper
            t_end = t0 + seconds
            latency, late = None, 0.0
            records = [(i, r) for i, r, _, _ in done]
    counter.armed = False
    pauses.armed = False
    pauses.close()
    log(f"collections in the window: {pauses}")
    if trace:
        jax.profiler.stop_trace()
    memory_peak = max(int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in devices)
    await srv.stop()
    return {"t0": t0, "t_end": t_end, "latency": latency, "late_s": late,
            "records": records, "installs": installs, "setup_s": setup_s,
            "memory_peak": memory_peak, "swap_route": swap_route}


# ------------------------------------------------------------------ check
def check(routes, reqs, raw, width_bits, frac_bits, feature_bits=None):
    """Judge every answered packet against the reference.  Returns, per
    record, its wrong packets and the slot version it was judged against,
    then the wrong packets and the lost requests in all.  With
    ``feature_bits`` the control's labels are judged against the exact
    reference instead of the program's answers."""
    installs = raw["installs"]
    releases = np.asarray([x["t_release"] for x in installs])
    versions = [x["version"] for x in installs]
    swap_route = raw["swap_route"]
    # group packets by (route, version) so the reference runs once a group
    groups: dict[tuple[int, int], list[int]] = {}
    for k, (i, res) in enumerate(raw["records"]):
        if res is None or isinstance(res, BaseException):
            continue
        r = int(reqs.route[i])
        v = 0
        if swap_route is not None and routes[r] is swap_route:
            n = int(np.searchsorted(releases, res.t_dispatch, side="right"))
            v = versions[n - 1] if n else 0
        groups.setdefault((r, v), []).append(k)
    wrong = np.zeros(len(raw["records"]), np.int64)
    version = np.zeros(len(raw["records"]), np.int64)
    for (r, v), ks in groups.items():
        version[ks] = v
        m = routes[r].versions[v]
        rows = np.concatenate([reqs.rows[raw["records"][k][0]] for k in ks])
        X = m.pool[rows]
        want = reference.predict(m.desc, X, width_bits=width_bits,
                                 frac_bits=frac_bits)
        if feature_bits is not None:
            got = reference.predict(m.desc, X, width_bits=width_bits,
                                    frac_bits=frac_bits,
                                    feature_bits=feature_bits)
        else:
            got = np.concatenate([np.asarray(raw["records"][k][1].rslt)
                                  for k in ks])
        if got.shape != want.shape:
            wrong[ks] = reqs.size[[raw["records"][k][0] for k in ks]]
            continue
        bad = (got != want).astype(np.int64)
        lo = 0
        for k in ks:
            n = int(reqs.size[raw["records"][k][0]])
            wrong[k] = bad[lo:lo + n].sum()
            lo += n
    lost = sum(1 for _, res in raw["records"]
               if res is None or isinstance(res, BaseException))
    return wrong, version, int(wrong.sum()), lost


def witness(models: list[Model], width_bits: int, frac_bits: int):
    """A second witness for the reference's reading of each fitted model:
    the learner's own ``predict`` on every row of the model's pool.  Trees
    and forests agree on every row; an SVM on every row whose float scores
    all lie farther from zero than the fixed-point rounding can move them
    (half a unit of ``frac_bits`` for each product and the bias).  Returns
    the rows that disagree and the rows compared."""
    bad = rows = 0
    for m in models:
        want = reference.predict(m.desc, m.pool, width_bits=width_bits,
                                 frac_bits=frac_bits)
        got = np.asarray(m.model.predict(m.pool))
        sure = np.ones(len(want), bool)
        if m.desc["kind"] == "svm":
            margin = (m.desc["W"].shape[1] + 1) * 0.5 / (1 << frac_bits)
            scores = np.asarray(m.model.decision_values(m.pool))
            sure = (np.abs(scores) > margin).all(axis=1)
        bad += int((got[sure] != want[sure]).sum())
        rows += int(sure.sum())
    return bad, rows


def longest_pause(raw) -> tuple[float, float]:
    """The longest time inside the window in which no request was answered,
    and when it began (seconds after the window opened)."""
    done = sorted(res.t_done for _, res in raw["records"]
                  if res is not None and not isinstance(res, BaseException))
    t = np.asarray([raw["t0"]] + [x for x in done if x <= raw["t_end"]]
                   + [raw["t_end"]])
    gaps = np.diff(t)
    k = int(np.argmax(gaps))
    return float(gaps[k]), float(t[k] - raw["t0"])


# ---------------------------------------------------------------- metrics
def context(cell, routes, reqs, raw, wrong, version, seconds, trace_summary,
            kind, chips) -> dict:
    """What the metric readers read: per-request host-clock records of the
    window, the dispatches they formed, the installs, the trace summary and
    the work count."""
    recs = raw["records"]
    n = len(recs)
    ok = np.zeros(n, bool)
    size = np.zeros(n, np.int64)
    t_sub = np.full(n, np.nan)
    t_dis = np.full(n, np.nan)
    t_done = np.full(n, np.nan)
    for k, (i, res) in enumerate(recs):
        size[k] = reqs.size[i]
        if res is None or isinstance(res, BaseException):
            continue
        ok[k] = wrong[k] == 0
        t_sub[k], t_dis[k], t_done[k] = res.t_submit, res.t_dispatch, \
            res.t_done
    # a dispatch's requests share its (t_dispatch, t_done) stamps
    answered = np.flatnonzero(~np.isnan(t_dis))
    keys = {}
    for k in answered:
        keys.setdefault((t_dis[k], t_done[k]), []).append(k)
    dispatches = []
    for (td, tdn), ks in keys.items():
        groups: dict = {}
        for k in ks:
            r = int(reqs.route[recs[k][0]])
            m = routes[r].versions[int(version[k])]
            cnt, cost = groups.get((r, m.name), (0, m.cost))
            groups[(r, m.name)] = (cnt + int(size[k]), cost)
        dispatches.append({
            "t_dispatch": td, "t_done": tdn,
            "packets": int(size[ks].sum()),
            "bytes": workcount.dispatch_bytes(groups)})
    return {
        "cell": cell["name"], "loop": cell["traffic"]["loop"],
        "seconds": seconds, "t0": raw["t0"], "t_end": raw["t_end"],
        "size": size, "ok": ok, "t_submit": t_sub, "t_dispatch": t_dis,
        "t_done": t_done, "latency": raw["latency"],
        "dispatches": dispatches, "installs": raw["installs"],
        "setup_s": raw["setup_s"], "trace": trace_summary,
        "peaks": peaks(kind), "chips": chips,
    }


# ------------------------------------------------------------------- main
def require_chips(n: int):
    """The TPU devices, or exit without a result."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"bench: no accelerator: {e}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found "
                         f"{devices[0].platform!r}")
    if len(devices) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX found "
                         f"{len(devices)}")
    return devices


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    when set, else at the fixed ``<checkout>/.jax_cache``."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    # every program set-up compiles, the install's small ones too, so that
    # only a cell's first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices,
             *, control_bits: int | None = None) -> dict:
    """Run one cell on ``devices`` and return its result line (a dict)."""
    config, t = cell["config"], cell["traffic"]
    used = list(devices[:cell["chips"]])
    kind = used[0].device_kind
    peaks(kind)                       # an unknown chip fails before the run
    counter = CompileCounter()
    log(f"{len(devices)} x {kind}")
    t_f = time.perf_counter()
    models = fit_models(config, seed)
    fit_s = time.perf_counter() - t_f
    for m in models:
        log(f"model {m.name} {m.pipeline}/{m.vid}"
            f"{' standby' if m.standby else ''}: {json.dumps(m.fp)}")
    log(f"fit {len(models)} models in {fit_s:.3f} s")
    zoo = build_server(config, models)
    log("installed")
    routes = make_routes(t, models)
    n = tr.n_requests(t, seconds)
    reqs = tr.make_requests(t, n, seed,
                            [len(r.versions[0].pool) for r in routes])
    feats = [routes[r].versions[0].pool[rows]
             for r, rows in zip(reqs.route, reqs.rows)]
    raw = asyncio.run(serve(cell, zoo, routes, reqs, feats,
                            seconds, trace, used, counter))
    log(f"setup {raw['setup_s']:.3f} s; window compiles/cache loads: "
        f"{counter.count}; generator late by at most "
        f"{raw['late_s'] * 1e3:.3f} ms")
    del zoo
    gc.collect()
    width = config["profile"]["feature_width"]
    frac = config["svm_frac_bits"]
    wrong, version, n_wrong, lost = check(routes, reqs, raw, width, frac)
    n_witness, witness_rows = witness(models, width, frac)
    pause, at = longest_pause(raw)
    stalls = [x["t_release"] - x["t_hold"] for x in raw["installs"]]
    log(f"{len(raw['records'])} requests; longest pause between answers "
        f"{pause * 1e3:.3f} ms at +{at:.3f} s; {len(stalls)} installs, "
        f"stall {min(stalls, default=0) * 1e3:.3f}-"
        f"{max(stalls, default=0) * 1e3:.3f} ms; reference against the "
        f"learners: {n_witness} of {witness_rows} rows differ")
    summary = None
    if trace:
        events = tracing.load(str(TRACE_DIR), [d.id for d in used])
        summary = tracing.reduce(events, KERNEL_NAMES)
        tracing.save(events, str(TRACE_DIR / "events.json.gz"))
    ctx = context(cell, routes, reqs, raw, wrong, version, seconds, summary,
                  kind, len(used))
    names = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for name in names:
        got = reader(name)(ctx)
        if got is not None:
            metrics[name] = {"value": got[0], "unit": got[1]}
    checks = {"wrong_answers": [n_wrong, 0], "lost_requests": [lost, 0],
              "reference_vs_learner": [n_witness, 0]}
    if control_bits is not None:
        _, _, c_wrong, _ = check(routes, reqs, raw, width, frac, control_bits)
        checks["control_wrong_answers"] = [c_wrong, 0]
    attempted = len(raw["records"])
    failed = int(sum(1 for k, (_, res) in enumerate(raw["records"])
                     if res is None or isinstance(res, BaseException)
                     or wrong[k]))
    device = {"platform": used[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": raw["memory_peak"]}
    out = {"correct": n_wrong == 0 and lost == 0 and n_witness == 0,
           "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = float(np.mean(list(summary["busy_s"].values())))
        device["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devices = require_chips(cell["chips"])
    use_compile_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
