"""Telemetry layer (repro.obs): registry window deltas, schema-validated
JSONL streams, span balance across threads, the Perfetto trace sink, the
reporter CLI, and the zero-overhead-when-disabled contract."""
import json
import threading

import numpy as np
import pytest

from repro.core.cliques import topology_matrix
from repro.core.planner import build_plan
from repro.core.unified_cache import TrafficCounter
from repro.graph.csr import powerlaw_graph
from repro.models.gnn import GNNConfig
from repro.obs import (SCHEMA_VERSION, Telemetry, TelemetryConfig,
                       activity_count, flat_name, maybe_span,
                       sum_counter_deltas, validate_stream)
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.report import digest, load_stream, main as report_main
from repro.obs.schema import TelemetrySchemaError, validate_line
from repro.obs.sinks import ChromeTraceSink
from repro.train.loop import train_gnn


# ---------------- registry ----------------

def test_counter_window_deltas_telescope():
    reg = MetricsRegistry()
    c = reg.counter("x")
    c.inc(5)
    counters, _, _ = reg.window_snapshot()
    assert counters["x"] == {"total": 5, "delta": 5}
    c.inc(3)
    counters, _, _ = reg.window_snapshot()
    assert counters["x"] == {"total": 8, "delta": 3}
    counters, _, _ = reg.window_snapshot()  # idle window
    assert counters["x"] == {"total": 8, "delta": 0}


def test_set_total_monotonic():
    reg = MetricsRegistry()
    c = reg.counter("t")
    c.set_total(10)
    with pytest.raises(ValueError, match="backwards"):
        c.set_total(9)


def test_counter_memoized_by_labels():
    reg = MetricsRegistry()
    assert reg.counter("b", tier="pcie") is reg.counter("b", tier="pcie")
    assert reg.counter("b", tier="pcie") is not reg.counter("b", tier="peer")


def test_flat_name_sorts_labels():
    assert flat_name("m", {}) == "m"
    assert flat_name("m", {"b": 1, "a": "x"}) == "m{a=x,b=1}"


def test_histogram_buckets_and_deltas():
    reg = MetricsRegistry()
    h = reg.histogram("d", edges=(1.0, 10.0))
    for v in (0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    _, _, hists = reg.window_snapshot()
    snap = hists["d"]
    assert snap["edges"] == [1.0, 10.0]
    assert snap["counts"] == [2, 1, 1]  # <=1, <=10, +inf overflow
    assert snap["delta"] == [2, 1, 1]
    assert snap["count"] == 4 and snap["sum"] == pytest.approx(56.0)
    h.observe(0.1)
    _, _, hists = reg.window_snapshot()
    assert hists["d"]["delta"] == [1, 0, 0]
    assert hists["d"]["counts"] == [3, 1, 1]


def test_histogram_edge_validation():
    with pytest.raises(ValueError, match="strictly increasing"):
        Histogram(())
    with pytest.raises(ValueError, match="strictly increasing"):
        Histogram((1.0, 1.0))
    reg = MetricsRegistry()
    reg.histogram("h", edges=(1.0, 2.0))
    with pytest.raises(ValueError, match="different edges"):
        reg.histogram("h", edges=(1.0, 3.0))


def test_histogram_quantile_against_numpy():
    """Interpolated bucket quantiles track np.percentile to within the
    containing bucket's width (the best a histogram can promise)."""
    edges = tuple(float(e) for e in np.linspace(0.1, 10.0, 34))
    h = Histogram(edges)
    rng = np.random.default_rng(7)
    samples = rng.gamma(shape=2.0, scale=1.5, size=5000).clip(0.01, 9.9)
    for v in samples:
        h.observe(float(v))
    for q in (0.01, 0.25, 0.50, 0.75, 0.90, 0.99):
        got = h.quantile(q)
        want = float(np.percentile(samples, 100 * q))
        i = int(np.searchsorted(np.asarray(edges), want))
        lo = 0.0 if i == 0 else edges[i - 1]
        hi = edges[min(i, len(edges) - 1)]
        assert abs(got - want) <= (hi - lo) + 1e-9, (q, got, want)


def test_histogram_quantile_edge_cases():
    h = Histogram((1.0, 2.0))
    assert h.quantile(0.5) is None  # empty
    h.observe(0.5)
    assert h.quantile(0.0) == pytest.approx(0.0)   # interpolates from 0
    assert h.quantile(1.0) == pytest.approx(1.0)   # top of first bucket
    h.observe(100.0)  # +inf overflow bucket has no upper edge:
    assert h.quantile(1.0) == pytest.approx(2.0)   # clamps to last edge
    with pytest.raises(ValueError, match="quantile"):
        h.quantile(1.5)


def test_sum_counter_deltas_filters_by_prefix():
    snaps = [{"counters": {"a.x": {"total": 1, "delta": 1},
                           "b.y": {"total": 2, "delta": 2}}},
             {"counters": {"a.x": {"total": 4, "delta": 3}}}]
    assert sum_counter_deltas(snaps) == {"a.x": 4, "b.y": 2}
    assert sum_counter_deltas(snaps, name="a.") == {"a.x": 4}


# ---------------- schema ----------------

def test_schema_rejects_malformed_lines():
    ok = {"v": SCHEMA_VERSION, "kind": "span", "name": "s", "ts_us": 1.0,
          "dur_us": 2.0, "tid": 7, "thread": "main"}
    assert validate_line(ok) == "span"
    for breakage, patch in [
            ("unknown kind", {"kind": "nope"}),
            ("extra field", {"bogus": 1}),
            ("wrong type", {"ts_us": "late"}),
            ("bool as number", {"dur_us": True}),
            ("negative duration", {"dur_us": -1.0}),
            ("future schema", {"v": SCHEMA_VERSION + 1})]:
        bad = dict(ok, **patch)
        with pytest.raises(TelemetrySchemaError):
            validate_line(bad)
    with pytest.raises(TelemetrySchemaError, match="name"):
        validate_line({k: v for k, v in ok.items() if k != "name"})


def test_snapshot_line_shape_enforced():
    line = {"v": SCHEMA_VERSION, "kind": "snapshot", "step": 5,
            "from_step": 0, "ts_us": 1.0,
            "counters": {"c": {"total": 3, "delta": 3}},
            "gauges": {"g": 1.5},
            "hists": {"h": {"edges": [1.0], "counts": [1, 0],
                            "delta": [1, 0], "sum": 0.5, "count": 1}}}
    assert validate_line(line) == "snapshot"
    bad = dict(line, counters={"c": {"total": 3}})  # missing delta
    with pytest.raises(TelemetrySchemaError):
        validate_line(bad)
    bad = dict(line, hists={"h": {"edges": [1.0], "counts": [1],
                                  "delta": [1], "sum": 0.5, "count": 1}})
    with pytest.raises(TelemetrySchemaError):  # counts must be edges+1 long
        validate_line(bad)


def test_stream_must_start_with_meta():
    span = {"v": SCHEMA_VERSION, "kind": "span", "name": "s", "ts_us": 0.0,
            "dur_us": 1.0, "tid": 1, "thread": "t"}
    with pytest.raises(TelemetrySchemaError, match="meta"):
        validate_stream([span])


def test_window_config_validated():
    with pytest.raises(ValueError, match="window"):
        TelemetryConfig(window=0)


# ---------------- zero-overhead contract ----------------

def test_disabled_path_runs_no_telemetry_code():
    before = activity_count()
    ctx = maybe_span(None, "anything", step=3)
    with ctx:
        pass
    assert maybe_span(None, "x") is ctx  # shared singleton, no allocation
    assert activity_count() == before


def test_enabled_spans_bump_activity():
    tele = Telemetry(TelemetryConfig(jax_annotations=False))
    before = activity_count()
    with maybe_span(tele, "work"):
        pass
    assert activity_count() == before + 1
    tele.close()


# ---------------- spans across threads ----------------

def test_span_balance_across_threads(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    tele = Telemetry(TelemetryConfig(jsonl_path=path, jax_annotations=False))

    def worker(i):
        with tele.span("outer", step=i, dev=i):
            with tele.span("inner", step=i):
                pass

    threads = [threading.Thread(target=worker, args=(i,), name=f"w{i}")
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tele.open_spans == 0
    assert tele.span_count == 8
    tele.close()
    lines = load_stream(path)
    spans = [ln for ln in lines if ln["kind"] == "span"]
    assert len(spans) == 8
    # tids may be recycled across joined threads; names are unique here
    assert {s["thread"] for s in spans} == {f"w{i}" for i in range(4)}
    # per thread: spans are properly nested (disjoint or contained)
    for name in {s["thread"] for s in spans}:
        own = sorted((s for s in spans if s["thread"] == name),
                     key=lambda s: s["ts_us"])
        for a, b in zip(own, own[1:]):
            a_end = a["ts_us"] + a["dur_us"]
            contained = (b["ts_us"] >= a["ts_us"]
                         and b["ts_us"] + b["dur_us"] <= a_end + 1e-6)
            disjoint = b["ts_us"] >= a_end - 1e-6
            assert contained or disjoint


def test_dangling_span_reported_at_close(tmp_path):
    path = str(tmp_path / "dangle.jsonl")
    tele = Telemetry(TelemetryConfig(jsonl_path=path, jax_annotations=False))
    span = tele.span("never_exits")
    span.__enter__()
    tele.close()
    lines = load_stream(path)
    events = [ln for ln in lines if ln["kind"] == "event"]
    assert any(e["name"] == "dangling_spans" and e["attrs"]["count"] == 1
               for e in events)


# ---------------- trace sink ----------------

def test_chrome_trace_sink_caps_span_events(tmp_path):
    path = str(tmp_path / "trace.json")
    sink = ChromeTraceSink(path, max_events=2)
    for i in range(5):
        sink.add_span("s", float(i), 1.0, 1, "main", i, {})
    sink.add_counter("c", 0.0, 1.0)  # counters are not capped
    sink.close()
    trace = json.load(open(path))
    names = [e["name"] for e in trace["traceEvents"]]
    assert names.count("s") == 2
    assert names.count("c") == 1


# ---------------- end-to-end through train_gnn ----------------

@pytest.fixture(scope="module")
def tiny():
    g = powerlaw_graph(2000, 8, seed=3, feat_dim=16)
    plan = build_plan(g, topology_matrix("nv2"), mem_per_device=400_000,
                      batch_size=64, seed=0, fanouts=(4, 2))
    return g, plan


@pytest.fixture(scope="module")
def run(tiny, tmp_path_factory):
    g, plan = tiny
    d = tmp_path_factory.mktemp("telem")
    jsonl, trace = str(d / "run.jsonl"), str(d / "run.json")
    cfg = GNNConfig(feat_dim=16, hidden=8, batch_size=64, fanouts=(4, 2))
    counter = TrafficCounter.for_plan(plan)
    tele = Telemetry(TelemetryConfig(jsonl_path=jsonl, trace_path=trace,
                                     window=4, run="test"))
    res = train_gnn(g, plan, cfg, steps=10, seed=0, counter=counter,
                    telemetry=tele)
    return res, counter, jsonl, trace


def test_stream_validates_and_result_reports(run):
    res, _, jsonl, trace = run
    lines = load_stream(jsonl)  # validates every line against the schema
    assert lines[0]["kind"] == "meta" and lines[0]["run"] == "test"
    assert res.telemetry["jsonl_path"] == jsonl
    assert res.telemetry["trace_path"] == trace
    assert res.telemetry["open_spans"] == 0
    assert res.telemetry["spans"] > 0


def test_window_deltas_reconstruct_final_totals(run):
    _, counter, jsonl, _ = run
    snaps = [ln for ln in load_stream(jsonl) if ln["kind"] == "snapshot"]
    assert len(snaps) >= 3  # 10 steps, window 4 -> 2 in-loop + 1 final
    sums = sum_counter_deltas(snaps)
    final = snaps[-1]["counters"]
    for key, c in final.items():
        assert sums[key] == c["total"], key
    assert final["traffic.feature_requests"]["total"] \
        == counter.feature_requests
    assert final["traffic.pcie_transactions"]["total"] \
        == counter.pcie_transactions
    # per-pair byte deltas reconstruct the full bytes matrix
    pair_sums = sum_counter_deltas(snaps, name="traffic.feat_bytes_pair{")
    total_pair = sum(pair_sums.values())
    assert total_pair == int(counter.bytes_matrix.sum())


def test_trace_loads_in_perfetto_shape(run):
    _, _, _, trace_path = run
    trace = json.load(open(trace_path))
    ev = trace["traceEvents"]
    steps = [e for e in ev if e.get("ph") == "X"
             and e.get("name") == "device_step"]
    assert len(steps) == 10
    assert all(e["dur"] >= 0 for e in steps)
    assert any(e.get("ph") == "M" and e.get("name") == "thread_name"
               for e in ev)
    assert any(e.get("ph") == "C" for e in ev)  # counter tracks


def test_telemetry_does_not_perturb_training(tiny):
    g, plan = tiny
    cfg = GNNConfig(feat_dim=16, hidden=8, batch_size=64, fanouts=(4, 2))
    r0 = train_gnn(g, plan, cfg, steps=6, seed=0)
    tele = Telemetry(TelemetryConfig(jax_annotations=False))
    r1 = train_gnn(g, plan, cfg, steps=6, seed=0, telemetry=tele)
    np.testing.assert_array_equal(r0.losses, r1.losses)
    assert r0.telemetry == {}


def test_result_telemetry_empty_when_disabled(tiny):
    g, plan = tiny
    cfg = GNNConfig(feat_dim=16, hidden=8, batch_size=64, fanouts=(4, 2))
    before = activity_count()
    res = train_gnn(g, plan, cfg, steps=4, seed=0)
    assert res.telemetry == {}
    assert activity_count() == before  # zero-overhead contract


# ---------------- reporter CLI ----------------

def test_reporter_digest_and_human_output(run, capsys):
    _, counter, jsonl, _ = run
    assert report_main([jsonl]) == 0
    out = capsys.readouterr().out
    assert "device steps" in out and "where the time went" in out
    assert report_main([jsonl, "--json"]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["device_steps"] == 10
    assert d["run"] == "test"
    assert d["final_counters"]["traffic.feature_requests"] \
        == counter.feature_requests
    assert all(w["feat_hit_rate"] is None or 0 <= w["feat_hit_rate"] <= 1
               for w in d["windows"])


def test_reporter_rejects_corrupt_stream(tmp_path, capsys):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"v": 1, "kind": "meta", "run": "x", "window": 1, '
                   '"t0_unix_s": 0.0, "pid": 1}\n{"not": "a line"}\n')
    assert report_main([str(bad)]) == 1
    assert "error:" in capsys.readouterr().err
    missing = tmp_path / "missing.jsonl"
    assert report_main([str(missing)]) == 1


def test_digest_queue_dry_and_spans(run):
    _, _, jsonl, _ = run
    d = digest(load_stream(jsonl))
    assert d["spans"]["device_step"]["count"] == 10
    assert d["train_loop_s"] > 0
    assert d["queue_dry_s"] >= 0


def test_reporter_prints_histogram_quantiles(run, capsys):
    """Every histogram in the stream shows up in the digest and the
    human report with interpolated p50/p99."""
    _, _, jsonl, _ = run
    d = digest(load_stream(jsonl))
    assert "step.time_s" in d["histograms"]
    h = d["histograms"]["step.time_s"]
    assert h["count"] == 10
    assert h["p50"] is not None and h["p99"] is not None
    assert h["p50"] <= h["p99"]
    assert report_main([jsonl]) == 0
    out = capsys.readouterr().out
    assert "histograms (interpolated quantiles)" in out
    assert "step.time_s" in out


# ---------------- build-stage spans and step inheritance ----------------

class _Recording(Telemetry):
    """Keeps every completed span in memory, as (name, t0, dur, thread,
    step, attrs)."""

    def __init__(self):
        super().__init__(TelemetryConfig(jax_annotations=False))
        self.recs = []

    def _record_span(self, name, t0_ns, dur_ns, tid, thread, step, attrs):
        super()._record_span(name, t0_ns, dur_ns, tid, thread, step, attrs)
        self.recs.append((name, t0_ns, dur_ns, thread, step, dict(attrs)))


LEAVES = ("sample_dispatch", "sample_sync", "sample_repair",
          "sample_account", "spec_dedup", "fill_split", "fill_miss")


def _half_cached():
    """A graph and a cache over half its vertices (by degree), so the
    device sampler's resolve repairs rows from the mirror and from the
    host CSR, and the fill has misses."""
    from repro.core.unified_cache import CliqueCache

    g = powerlaw_graph(3000, 8, seed=9, feat_dim=16)
    order = np.argsort(-(g.indptr[1:] - g.indptr[:-1]), kind="stable")
    ids = np.sort(order[: g.n // 2]).astype(np.int64)
    parts = np.array_split(ids, 4)
    return g, CliqueCache(g, list(range(4)), [p[:8] for p in parts], parts,
                          topology_mode="sharded")


def test_span_step_inherits_from_innermost_open_span():
    tele = _Recording()
    with tele.span("outer", step=4):
        with tele.span("mid"):
            with tele.span("leaf"):
                pass
        with tele.span("own", step=9):
            with tele.span("under_own"):
                pass
    with tele.span("root"):
        pass
    steps = {r[0]: r[4] for r in tele.recs}
    assert steps == {"outer": 4, "mid": 4, "leaf": 4, "own": 9,
                     "under_own": 9, "root": None}
    assert tele.open_spans == 0
    tele.close()


def test_device_build_emits_the_seven_leaf_spans_in_order():
    from repro.train.batch import DeviceBatchBuilder

    g, cache = _half_cached()
    tele = _Recording()
    builder = DeviceBatchBuilder(g, cache, (5, 3), dev=0, gather="xla")
    builder.telemetry = tele
    rng = np.random.default_rng(0)
    for step in (3, 4):
        with tele.span("spec_build", step=step):
            spec = builder.build_spec(rng.integers(0, g.n, 64), rng,
                                      step=step)
        assert spec.step == step
    builds = [r for r in tele.recs if r[0] == "spec_build"]
    assert len(builds) == 2
    for b in builds:
        inside = sorted((r for r in tele.recs if r[0] != "spec_build"
                         and b[1] <= r[1] and r[1] + r[2] <= b[1] + b[2]),
                        key=lambda r: r[1])
        assert tuple(r[0] for r in inside) == LEAVES
        assert {r[4] for r in inside} == {b[4]}  # the parent's step
        assert {r[3] for r in inside} == {b[3]}  # the parent's thread
        attrs = {r[0]: r[5] for r in inside}
        assert attrs["sample_dispatch"]["draws"] == 64 * 5 + 64 * 5 * 3
        assert attrs["sample_sync"]["rows"] == 64 + 64 * 5
        assert attrs["fill_miss"]["rows"] == attrs["fill_split"]["n_miss"]
        assert attrs["spec_dedup"]["n_ids"] >= attrs["fill_split"]["n_miss"]
    assert tele.open_spans == 0
    tele.close()


def test_spec_dedup_counts_sampled_and_unique_ids():
    from repro.train.batch import DeviceBatchBuilder

    g, cache = _half_cached()
    tele = _Recording()
    builder = DeviceBatchBuilder(g, cache, (5, 3), dev=0, gather="xla")
    builder.telemetry = tele
    rng = np.random.default_rng(1)
    spec = builder.sample_spec(rng.integers(0, g.n, 64), rng)
    (attrs,) = [r[5] for r in tele.recs if r[0] == "spec_dedup"]
    assert attrs["n_sampled"] == sum(int((l >= 0).sum())
                                     for l in spec.levels)
    assert attrs["n_ids"] == spec.n_ids == len(spec.ids)
    assert attrs["n_ids"] <= attrs["n_sampled"]
    tele.close()


def test_sample_repair_counts_the_rows_it_repaired(monkeypatch):
    from repro.graph import sampling
    from repro.train.batch import DeviceBatchBuilder

    g, cache = _half_cached()
    seen = {"mirror": 0, "host": 0}
    real_mirror, real_host = (sampling._mirror_sample_level,
                              sampling.host_sample_level)

    def mirror(cache_, seeds, fanout, rand):
        seen["mirror"] += len(seeds)
        return real_mirror(cache_, seeds, fanout, rand)

    def host(g_, seeds, fanout, rng, rand=None):
        seen["host"] += len(seeds)
        return real_host(g_, seeds, fanout, rng, rand=rand)

    monkeypatch.setattr(sampling, "_mirror_sample_level", mirror)
    monkeypatch.setattr(sampling, "host_sample_level", host)
    tele = _Recording()
    builder = DeviceBatchBuilder(g, cache, (5, 3), dev=0, gather="xla")
    builder.telemetry = tele
    rng = np.random.default_rng(1)
    for _ in range(3):
        builder.build_spec(rng.integers(0, g.n, 64), rng)
    reps = [r[5] for r in tele.recs if r[0] == "sample_repair"]
    assert len(reps) == 3
    assert sum(a["mirror_rows"] for a in reps) == seen["mirror"] > 0
    assert sum(a["host_rows"] for a in reps) == seen["host"] > 0
    tele.close()


def test_consumer_spans_carry_their_batch_step(tiny):
    g, plan = tiny
    cfg = GNNConfig(feat_dim=16, hidden=8, batch_size=64, fanouts=(4, 2))
    tele = _Recording()
    train_gnn(g, plan, cfg, steps=5, seed=0, backend="device", gather="xla",
              telemetry=tele)
    by = {}
    for r in tele.recs:
        by.setdefault(r[0], []).append(r)
    # one get per step; one build, finalize and staging copy per device
    n_dev = len(plan.partition.tablets)
    gets = sorted(by["prefetch_get"], key=lambda r: r[1])
    assert [r[4] for r in gets] == list(range(5))
    for name in ("finalize", "h2d_staging", "spec_build"):
        assert sorted(r[4] for r in by[name]) \
            == sorted(list(range(5)) * n_dev), name
    # each batch's finalizes follow the get that returned it
    for get in gets:
        fins = [r for r in by["finalize"] if r[4] == get[4]]
        assert len(fins) == n_dev
        assert all(get[1] + get[2] <= r[1] for r in fins)
    # every build leaf carries the step of the spec_build around it.
    # Each build holds its own batch's leaves once; its sample_dispatch
    # spans name the steps they draw for: the next step's (the sampling
    # lookahead), and its own only in the call's first build
    for b in by["spec_build"]:
        inside = [r for r in tele.recs if r[0] in LEAVES
                  and r[3] == b[3]
                  and b[1] <= r[1] and r[1] + r[2] <= b[1] + b[2]]
        assert sorted(r[0] for r in inside if r[0] != "sample_dispatch") \
            == sorted(set(LEAVES) - {"sample_dispatch"})
        assert {r[4] for r in inside} == {b[4]}
        k = b[4]
        assert sorted(r[5]["for_step"] for r in inside
                      if r[0] == "sample_dispatch") \
            == [k] * (k == 0) + [k + 1] * (k + 1 < 5)


def test_disabled_device_backend_runs_no_telemetry_code(tiny):
    g, plan = tiny
    cfg = GNNConfig(feat_dim=16, hidden=8, batch_size=64, fanouts=(4, 2))
    before = activity_count()
    res = train_gnn(g, plan, cfg, steps=3, seed=0, backend="device",
                    gather="xla")
    assert res.telemetry == {}
    assert activity_count() == before


def test_digest_self_time_subtracts_same_thread_children(tmp_path):
    """Self time is a span's duration less its direct children on the same
    thread: a grandchild counts against its parent only, and a span on
    another thread that overlaps in time counts against nothing."""
    path = tmp_path / "nested.jsonl"
    lines = [{"v": SCHEMA_VERSION, "kind": "meta", "run": "x", "window": 1,
              "t0_unix_s": 0.0, "pid": 1}]

    def span(name, ts, dur, tid):
        lines.append({"v": SCHEMA_VERSION, "kind": "span", "name": name,
                      "ts_us": float(ts), "dur_us": float(dur), "tid": tid,
                      "thread": f"t{tid}"})

    span("finalize", 0, 100, 1)
    span("h2d_staging", 10, 30, 1)
    span("leaf", 15, 5, 1)
    span("finalize", 200, 50, 1)
    span("other", 20, 60, 2)
    path.write_text("".join(json.dumps(ln) + "\n" for ln in lines))
    d = digest(load_stream(str(path)))
    assert d["spans"]["finalize"]["self_s"] == pytest.approx(120e-6)
    assert d["spans"]["h2d_staging"]["self_s"] == pytest.approx(25e-6)
    assert d["spans"]["leaf"]["self_s"] == pytest.approx(5e-6)
    assert d["spans"]["other"]["self_s"] == pytest.approx(60e-6)
    assert report_main([str(path)]) == 0
