"""The trace reducer on a small trace recorded on a TPU v5e (a tiny cell,
the parts the reducer reads kept as an XSpace text proto), and on a
synthetic case worked out by hand."""
import gzip
import os

import _benchpath  # noqa: F401
import pytest

from benchlib import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_trace.textproto.gz")


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with gzip.open(DATA, "rt") as f:
        pd = ProfileData.from_text_proto(f.read())
    return xplane.load_events(pd)


def test_recorded_trace_loads(recorded):
    ops, host = recorded
    assert list(ops) == ["/device:TPU:0"]
    assert len(ops["/device:TPU:0"]) == 2152
    names = {h[0] for h in host}
    assert {"device_step", "prefetch_get", "h2d_staging"} <= names


def test_recorded_trace_reduces(recorded):
    r = xplane.reduce_events(*recorded)
    steps = sum(1 for h in recorded[1] if h[0] == "device_step")
    # the window opens at the end of the call's first step
    assert steps == 11 and r["steps"] == 10
    # one fused-gather kernel call per finalized batch of the traced call
    assert r["kernels"]["fused_gather"]["calls"] == 11
    assert 0 < r["kernels"]["fused_gather"]["device_s"] < r["busy_s"]
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["collective_s"] == 0.0  # one chip: no collectives
    bd = r["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0] == \
        "jit_fused_finalize:%fused_finalize.1(kernel)"
    assert {k for k, _ in bd["idle_gaps"]} <= set(xplane.HOST_SPANS)
    # idle time, whatever the host did, adds up to the window less busy
    idle = sum(v for _, v in bd["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


def test_synthetic_by_hand():
    ms = 1_000_000
    kernel = ('%fused_finalize.1 = f32[8,128] custom-call(%a), '
              'custom_call_target="tpu_custom_call"')
    ops = {"/device:TPU:0": [
        (kernel, 10 * ms, 20 * ms, "jit_fused_finalize"),
        ("%fusion.1 = f32[8] fusion(%x)", 15 * ms, 30 * ms, "jit_step"),
        ("%all-reduce.2 = f32[8] all-reduce(%y)", 50 * ms, 60 * ms,
         "jit_step"),
        ("%fusion.3 = f32[8] fusion(%z)", 55 * ms, 58 * ms, "jit_step")]}
    host = [("device_step", 0, 5 * ms, "main"),
            ("device_step", 5 * ms, 100 * ms, "main"),
            ("prefetch_get", 31 * ms, 49 * ms, "main")]
    r = xplane.reduce_events(ops, host)
    assert r["window_s"] == pytest.approx(0.095)
    assert r["busy_s"] == pytest.approx(0.030)  # [10,30] and [50,60]
    assert r["collective_s"] == pytest.approx(0.010)
    assert r["collective_exposed_s"] == pytest.approx(0.007)
    assert r["kernels"]["fused_gather"] == {"calls": 1,
                                            "device_s": pytest.approx(0.01)}
    gaps = dict(r["breakdown"]["idle_gaps"])
    # [5,10] and [60,100] fall in device_step alone; [30,50] in prefetch_get
    assert gaps["prefetch_get"] == pytest.approx(0.020)
    assert gaps["device_step"] == pytest.approx(0.045)


def test_window_can_close_before_the_last_step(recorded):
    full = xplane.reduce_events(*recorded)
    part = xplane.reduce_events(*recorded, window_steps=4)
    assert part["steps"] == 4 and part["window_s"] < full["window_s"]
    # the kernel is counted over the whole traced call either way
    assert part["kernels"] == full["kernels"]


def test_window_steps_closes_at_seconds():
    from benchlib.harness import window_steps

    ends = [0, 400, 900, 1300, 2100, 2500]  # ms
    ns = [t * 1_000_000 for t in ends]
    assert window_steps(ns, 1.3) == 3  # 1300 ms after the first end
    assert window_steps(ns, 1.31) == 4
    assert window_steps(ns, 9.0) == 5  # never reached: every step
