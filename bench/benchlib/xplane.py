"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy and idle time over the window, kernel device time,
collective time, and the breakdown of device ops and idle gaps.

Planes whose name starts with ``/device:TPU:`` are chips; their ``XLA
Ops`` line holds one event per executed HLO op.  The host plane's lines
hold the program's spans (``repro.obs`` bridges each into a
``TraceAnnotation`` of the same name when the run traces), which set the
window and name what the host was doing in each idle gap.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# kernel name in BENCHMARK.json terms -> pattern on the op event's name,
# which is the HLO instruction's text: the Pallas call is a tpu_custom_call
# named after the jitted function that holds it
KERNELS = {"fused_gather": re.compile(
    r'^%fused_finalize[.\d]* = .*custom_call_target="tpu_custom_call"')}
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|psum|ppermute", re.I)
# the program's spans: what the host can be doing while the chip idles
HOST_SPANS = ("prefetch_get", "h2d_staging", "finalize", "device_step",
              "pipeline_prime", "spec_build", "prefetch_build",
              "refresh_hook", "train_loop")
WINDOW_SPAN = "device_step"
TOP = 10


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def _module_of(mods, starts, t: int) -> str:
    """The name of the XLA module running at ``t`` ('' if none)."""
    i = bisect.bisect_right(starts, t) - 1
    return mods[i][2] if i >= 0 and t < mods[i][1] else ""


def _collective(name: str) -> bool:
    return bool(COLLECTIVE.search(name.split(" = ", 1)[0]))


def short_op(name: str, module: str) -> str:
    """``module:%op`` from an op event's HLO text."""
    op = name.split(" = ", 1)[0]
    if "tpu_custom_call" in name:
        op += "(kernel)"
    return f"{module}:{op}" if module else op


def _merge(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv, lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def load_events(pd):
    """From a ``jax.profiler.ProfileData``: (device ops per chip, host
    span events), times in ns on the trace's
    clock: ``ops[chip] = [(op text, start, end, module)]``, ``host =
    [(name, start, end, thread)]``."""
    ops: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = ops.setdefault(plane.name, [])
            lines = {line.name: line for line in plane.lines}
            mods = sorted((int(ev.start_ns), int(ev.start_ns)
                           + int(ev.duration_ns), ev.name.split("(")[0])
                          for ev in (lines[MODULES_LINE].events
                                     if MODULES_LINE in lines else ()))
            starts = [m[0] for m in mods]
            for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                s = int(ev.start_ns)
                evs.append((ev.name, s, s + int(ev.duration_ns),
                            _module_of(mods, starts, s)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        s = int(ev.start_ns)
                        host.append((ev.name, s, s + int(ev.duration_ns),
                                     line.name))
    return ops, host


def reduce_events(ops: Dict[str, list], host: list,
                  window_steps: Optional[int] = None) -> Optional[dict]:
    """The device numbers of one traced call (see module doc).  The window
    runs from the end of the call's first step to the end of step
    ``window_steps`` after it (default: the last).  Kernel calls and time
    count the whole call.  None when the trace holds no chip or no
    window."""
    steps = sorted((s, e) for n, s, e, _ in host if n == WINDOW_SPAN)
    if not ops or len(steps) < 2:
        return None
    last = len(steps) - 1 if window_steps is None else window_steps
    w0, w1 = steps[0][1], steps[last][1]
    window_s = (w1 - w0) / 1e9
    busy, coll, coll_exposed = [], [], []
    kernels = {k: {"calls": 0, "device_s": 0.0} for k in KERNELS}
    by_op: Dict[str, float] = {}
    gaps_all = []
    for chip, evs in ops.items():
        iv = _merge([(s, e) for _, s, e, _ in evs])
        win = _clip(iv, w0, w1)
        busy.append(sum(b - a for a, b in win) / 1e9)
        c_iv = _merge([(s, e) for n, s, e, _ in evs if _collective(n)])
        other = _merge([(s, e) for n, s, e, _ in evs
                        if not _collective(n)])
        c_win = _clip(c_iv, w0, w1)
        coll.append(sum(b - a for a, b in c_win) / 1e9)
        coll_exposed.append(_exposed(c_win, _clip(other, w0, w1)) / 1e9)
        for name, s, e, module in evs:
            for k, pat in KERNELS.items():
                if pat.search(name):
                    kernels[k]["calls"] += 1
                    kernels[k]["device_s"] += (e - s) / 1e9
            a, b = max(s, w0), min(e, w1)
            if b > a:
                op = short_op(name, module)
                by_op[op] = by_op.get(op, 0.0) + (b - a) / 1e9
        prev = w0
        for a, b in win + [(w1, w1)]:
            if a > prev:
                gaps_all.append((prev, a))
            prev = max(prev, b)
    n = len(ops)
    idle: Dict[str, float] = {}
    for a, b in gaps_all:
        what = _host_activity(host, a, b)
        idle[what] = idle.get(what, 0.0) + (b - a) / 1e9 / n
    top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"window_s": window_s, "busy_s": sum(busy) / n,
            "collective_s": sum(coll) / n,
            "collective_exposed_s": sum(coll_exposed) / n,
            "kernels": kernels, "steps": last,
            "breakdown": {"device_ops": top({k: v / n
                                             for k, v in by_op.items()}),
                          "idle_gaps": top(idle)}}


def _exposed(coll, other) -> int:
    """Collective time during which no other op runs on that chip."""
    total = 0
    for a, b in coll:
        covered = sum(min(b, d) - max(a, c) for c, d in other
                      if d > a and c < b)
        total += (b - a) - covered
    return total


def _host_activity(host: list, a: int, b: int) -> str:
    """What the host was doing in the gap [a, b]: the innermost program
    span that covers at least half of it, else the one covering most."""
    inner, inner_len, most, most_cover = None, None, "no span", 0
    for name, s, e, _ in host:
        cover = min(b, e) - max(a, s)
        if cover <= 0:
            continue
        if 2 * cover >= b - a and (inner_len is None or e - s < inner_len):
            inner, inner_len = name, e - s
        if cover > most_cover:
            most, most_cover = name, cover
    return inner if inner is not None else most


def reduce(trace_dir: str, window_steps: Optional[int] = None
           ) -> Optional[dict]:
    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_events(*load_events(ProfileData.from_file(path)),
                         window_steps)
