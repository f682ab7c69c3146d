"""The readers of the batch build's stage spans and of finalize's self
time, on span lists made by hand: only spans that end inside the window
count, the mean is per span, and finalize loses only the ``h2d_staging``
it contains on its own thread."""
import json
import os

import _benchpath  # noqa: F401
import pytest

from _benchpath import BENCH, ROOT
from benchlib import harness
from benchlib.telemetry import SpanRec

MS = 1_000_000
STAGES = {"sampler.dispatch_ms": "sample_dispatch",
          "sampler.sync_ms": "sample_sync",
          "sampler.repair_ms": "sample_repair",
          "sampler.account_ms": "sample_account",
          "spec.dedup_ms": "spec_dedup",
          "fill.split_ms": "fill_split",
          "fill.miss_ms": "fill_miss"}


def reader(name: str):
    return harness.load_module(os.path.join(BENCH, "metrics", f"{name}.py"),
                               "test_metric_" + name.replace(".", "_"))


def run_of(spans, window=(100 * MS, 200 * MS)):
    run = harness.Run()
    run.window_ns = window
    run.spans = spans
    return run


def span(name, t0_ms, dur_ms, thread="prefetch"):
    return SpanRec(name, int(t0_ms * MS), int(dur_ms * MS), thread, 1, {})


def test_every_new_reader_is_a_declared_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in list(STAGES) + ["finalize.host_ms"]:
        assert declared[name]["source"] == "program_span"
        assert declared[name]["moves"] == "seeds_per_s"


@pytest.mark.parametrize("metric", sorted(STAGES))
def test_stage_reader_means_spans_ending_in_window(metric):
    name = STAGES[metric]
    spans = [span(name, 90, 5),      # ends at 95: before the window
             span(name, 95, 10),     # ends at 105: inside
             span(name, 150, 50),    # ends at 200, the window's end: inside
             span(name, 190, 20),    # ends at 210: after
             span("other", 120, 70)]
    assert reader(metric).read(run_of(spans)) == pytest.approx(30.0)
    assert reader(metric).read(run_of([span("other", 120, 7)])) is None


def test_finalize_host_ms_subtracts_same_thread_staging():
    spans = [span("finalize", 110, 20, "main"),
             span("h2d_staging", 112, 6, "main"),     # inside, same thread
             span("h2d_staging", 115, 4, "prefetch"),  # other thread
             span("finalize", 150, 10, "main"),
             span("h2d_staging", 158, 5, "main"),     # runs past its end
             span("finalize", 50, 10, "main"),        # before the window
             span("h2d_staging", 52, 3, "main")]
    # (20 - 6 + 10) / 2
    got = reader("finalize.host_ms").read(run_of(spans))
    assert got == pytest.approx(12.0)
    assert reader("finalize.host_ms").read(run_of(spans[1:3])) is None
