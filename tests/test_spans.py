"""The program's own spans, dispatch and hold records, and runtime counters.

One short ``ContinuousZooServer`` window is served under
``jax.profiler.trace`` and read back with ``ProfileData``, as the
benchmark reads a chip's trace: ragged traffic, a drain -> install ->
release under load, more traffic, then one request past the warmed bucket
ladder.  The tests pin what a trace reader relies on: every span of
``repro.core.spans.SPANS`` appears and no other ``acorn.*`` name does,
per-dispatch spans carry the ids of the front's dispatch records, the
records' stamps are ordered, the runtime's counters add up to the traffic,
the one dispatch that traced is tagged ``compiled=1``, and the span that
ends a drained hold carries its drain.
"""
import asyncio
import glob
import os
import sys
import threading

import jax
import numpy as np
import pytest

from repro.core.mlmodels import DecisionTree
from repro.core.packets import PacketBatch
from repro.core.plane import PlaneProfile
from repro.core.spans import SPANS, span
from repro.runtime import DataplaneRuntime, SizeOrDeadlinePolicy, bucket_size
from repro.serving import AsyncZooServer, ContinuousZooServer, ZooServer

DISPATCH_SPANS = ("acorn.coalesce", "acorn.pad", "acorn.launch",
                  "acorn.fetch")
SIZES = (1, 3, 5, 7, 2, 6, 4, 1, 8, 3)       # ragged, all under max_batch
MAX_BATCH = 16


def _profile():
    return PlaneProfile(max_features=36, max_trees=4, max_layers=6,
                        max_entries_per_layer=64, max_leaves=64,
                        max_classes=8, max_hyperplanes=8, max_versions=2)


def _program_spans(trace_dir):
    """``[(name, start_ns, end_ns, stats)]`` of every ``acorn.*`` event."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats))
                    for e in line.events if e.name.startswith("acorn.")]
    return out


@pytest.fixture(scope="module")
def served(satdap, tmp_path_factory):
    Xtr, ytr, Xte, _ = satdap
    zoo = ZooServer(_profile())
    zoo.install(DecisionTree(max_depth=4, max_leaf_nodes=16).fit(Xtr, ytr),
                vid=0)
    retrain = DecisionTree(max_depth=5, max_leaf_nodes=24).fit(Xtr, ytr)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    policy = SizeOrDeadlinePolicy(max_batch=MAX_BATCH, max_wait_us=2_000)

    async def wave(srv, lo):
        await asyncio.gather(*[
            srv.submit(Xte[lo + i:lo + i + n], mid=0, vid=0)
            for i, n in enumerate(SIZES)])
        return sum(SIZES)

    async def main():
        async with ContinuousZooServer(zoo, policy=policy, n_slots=2) as srv:
            ladder = srv.warmed_buckets
            after_warm = srv.latency_stats()["runtime"]
            with jax.profiler.trace(trace_dir):
                n1 = await wave(srv, 0)
                t_hold = asyncio.get_running_loop().time()
                await srv.drain()
                held = asyncio.get_running_loop().time() - t_hold
                srv.install(retrain, vid=1, tag="retrain")
                srv.release()
                n2 = await wave(srv, 20)
                warmed = srv.latency_stats()
                # one request past the warmed ladder: its bucket traces
                big = await srv.submit(Xte[:2 * MAX_BATCH], mid=0, vid=1)
            return {"ladder": ladder, "after_warm": after_warm,
                    "warmed": warmed, "stats": srv.latency_stats(),
                    "records": tuple(srv._dispatch_log), "held": held,
                    "packets": n1 + n2 + 2 * MAX_BATCH, "big": big}

    out = asyncio.run(main(), debug=True)
    out["spans"] = _program_spans(trace_dir)
    out["zoo"], out["Xte"] = zoo, Xte
    return out


def test_every_span_appears_and_no_other(served):
    names = {n for n, *_ in served["spans"]}
    assert names == set(SPANS)


def test_dispatch_spans_carry_the_records_ids(served):
    ids = {r.id for r in served["records"]}
    assert len(ids) == len(served["records"])
    for name in DISPATCH_SPANS:
        got = sorted(st["dispatch"] for n, _, _, st in served["spans"]
                     if n == name)
        assert sorted(ids) == got, name      # one span a dispatch, each id


def test_run_host_spans_follow_each_other(served):
    """Within one dispatch the host path's spans run in order, on one
    thread, after its coalesce."""
    by = {}
    for n, a, b, st in served["spans"]:
        if n in DISPATCH_SPANS:
            by.setdefault(st["dispatch"], {})[n] = (a, b)
    for spans in by.values():
        ends = [spans[n] for n in DISPATCH_SPANS]
        for (_, b), (a, _) in zip(ends, ends[1:]):
            assert b <= a


def test_records_stamps_are_ordered(served):
    for r in served["records"]:
        assert r.t_first_submit <= r.t_start <= r.t_done
        assert 1 <= r.rows_real


def test_counters_add_up_to_the_traffic(served):
    """Warm-up runs on its own facade, so the zoo's runtime counts only the
    served traffic: its real rows, the buckets they ran in."""
    assert served["after_warm"] == {"rows_real": 0, "rows_run": 0,
                                    "compiles": 0}
    c = served["stats"]["runtime"]
    recs = served["records"]
    assert c["rows_real"] == sum(r.rows_real for r in recs) \
        == served["packets"]
    assert c["rows_run"] == sum(bucket_size(r.rows_real) for r in recs)


def test_compiles_only_on_a_bucket_not_warmed(served):
    assert served["warmed"]["runtime"]["compiles"] == 0
    assert served["stats"]["runtime"]["compiles"] == 1
    assert 2 * MAX_BATCH not in served["ladder"]
    (big,) = [r for r in served["records"] if r.rows_real == 2 * MAX_BATCH]
    compiled = [st["dispatch"] for n, _, _, st in served["spans"]
                if n == "acorn.launch" and st.get("compiled")]
    assert compiled == [big.id]
    np.testing.assert_array_equal(
        served["big"].rslt,
        served["zoo"].classify(served["Xte"][:2 * MAX_BATCH], mid=0, vid=1))


def test_hold_record_and_release_span(served):
    """The release span ends the one drained hold and carries its drain,
    which lies inside the test's own stamps around ``drain()``."""
    (rel,) = [st for n, _, _, st in served["spans"] if n == "acorn.release"]
    assert set(rel) == {"drain_us"}
    assert 0 <= rel["drain_us"] <= served["held"] * 1e6 + 1
    installs = {n: st["vid"] for n, _, _, st in served["spans"]
                if n.startswith("acorn.install.")}
    assert installs == {"acorn.install.translate": 1,
                        "acorn.install.tables": 1, "acorn.install.write": 1}


def test_mean_batch_packets_is_real_packets_per_dispatch(served):
    recs = served["records"]
    assert served["stats"]["dispatches"] == len(recs)
    assert served["stats"]["mean_batch_packets"] == pytest.approx(
        served["packets"] / len(recs))


def test_pad_span_carries_rows_and_bucket(served):
    rows = {r.id: (r.rows_real, bucket_size(r.rows_real))
            for r in served["records"]}
    pads = {st["dispatch"]: (st["rows"], st["bucket"])
            for n, _, _, st in served["spans"] if n == "acorn.pad"}
    assert pads == rows


def test_span_names_are_the_known_ones():
    with pytest.raises(ValueError):
        span("acorn.unknown")
    assert all(n.startswith("acorn.") for n in SPANS)
    assert len(set(SPANS)) == len(SPANS)


def test_hold_records_of_a_bare_hold_and_a_broken_one(tmp_path):
    """A hold without drain() ends with a release span without a drain; a
    drained hold that stop() breaks ends with one that carries it, when
    stop() opens the gate, and its owner's release() still raises."""
    zoo = ZooServer(_profile())

    async def main():
        srv = AsyncZooServer(zoo)
        await srv.start()
        srv.hold()
        srv.release()
        await srv.drain()
        await srv.stop()
        with pytest.raises(RuntimeError):
            srv.release()

    with jax.profiler.trace(str(tmp_path)):
        asyncio.run(main(), debug=True)
    bare, broken = sorted(
        (a, st) for n, a, _, st in _program_spans(str(tmp_path))
        if n == "acorn.release")
    assert bare[1] == {} and set(broken[1]) == {"drain_us"}
    assert broken[1]["drain_us"] >= 0


def test_counters_hold_under_concurrent_launches(satdap):
    """Sixteen threads launch through one runtime with a tiny switch
    interval: every row is counted once, and each bucket's one trace once,
    however many threads saw it appear."""
    Xtr, ytr, Xte, _ = satdap
    zoo = ZooServer(_profile())
    zoo.install(DecisionTree(max_depth=4, max_leaf_nodes=16).fit(Xtr, ytr),
                vid=0)
    rt = DataplaneRuntime(zoo.executor)
    sizes = [1 + (i * 7) % 8 for i in range(16 * 6)]
    batches = [zoo.make_request(Xte[:n], mid=0, vid=0) for n in sizes]
    errors = []

    def work(k):
        try:
            for pb in batches[k::16]:
                out = rt.run_host(pb)
                assert isinstance(out, PacketBatch) and out.batch == pb.batch
        except Exception as e:          # surfaced by the assert below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors
    c = rt.counters()
    assert c["rows_real"] == sum(sizes)
    assert c["rows_run"] == sum(bucket_size(n) for n in sizes)
    assert c["compiles"] == len({bucket_size(n) for n in sizes}) \
        == zoo.executor.cache_size()
