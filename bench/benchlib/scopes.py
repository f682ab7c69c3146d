"""Device time of the ops that a ``jax.named_scope`` of the program covers.

An op carries a scope when the scope's name appears in its source op name
(HLO ``metadata={op_name=...}``), which holds the scopes the op was traced
under: ``jit(train_step_plain)/jvp(gat_attention)/mul`` in the forward
pass and ``.../transpose(jvp(gat_attention))/...`` in the backward, so one
name covers both.  A TPU trace gives that op name as the string ``tf_op``
stat of an ``XLA Ops`` event's metadata (one metadata entry per HLO op),
not of the event.  ``jax.profiler.ProfileData`` shows only the event's
own stats, so ``load_events`` reads the ``.xplane.pb`` itself, through
message classes for the few ``XSpace`` fields it needs (field numbers of
``tsl/profiler/protobuf/xplane.proto``; the rest is skipped unparsed).

The time is taken over the window ``benchlib.xplane.reduce_events`` uses:
from the end of the first ``device_step`` in the trace to the end of the
run's last window step, averaged over the chips, per window step.
"""
from __future__ import annotations

import functools
import os
from typing import Dict, List, Optional, Tuple

from benchlib import xplane

OP_NAME_STAT = "tf_op"


@functools.lru_cache(maxsize=1)
def xspace_class():
    """A message class for the parts of ``XSpace`` read here."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto",
                                             package="bench_xspace")

    def msg(name, *fields):
        """Scalars by type name; a message type repeats unless marked
        ``!`` (one value)."""
        m = fdp.message_type.add(name=name)
        for fname, num, ftype in fields:
            f = m.field.add(name=fname, number=num, label=F.LABEL_OPTIONAL)
            if ftype in ("int64", "uint64", "string"):
                f.type = getattr(F, "TYPE_" + ftype.upper())
                continue
            f.type = F.TYPE_MESSAGE
            f.type_name = ".bench_xspace." + ftype.rstrip("!")
            if not ftype.endswith("!"):
                f.label = F.LABEL_REPEATED

    msg("XStat", ("metadata_id", 1, "int64"), ("str_value", 5, "string"))
    msg("XEvent", ("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
        ("duration_ps", 3, "int64"))
    msg("XLine", ("name", 2, "string"), ("timestamp_ns", 3, "int64"),
        ("events", 4, "XEvent"))
    msg("XEventMetadata", ("id", 1, "int64"), ("name", 2, "string"),
        ("stats", 5, "XStat"))
    msg("XStatMetadata", ("id", 1, "int64"), ("name", 2, "string"))
    # a map<int64, V> field is on the wire a repeated (key 1, value 2) entry
    msg("EventMetadataEntry", ("key", 1, "int64"),
        ("value", 2, "XEventMetadata!"))
    msg("StatMetadataEntry", ("key", 1, "int64"),
        ("value", 2, "XStatMetadata!"))
    msg("XPlane", ("name", 2, "string"), ("lines", 3, "XLine"),
        ("event_metadata", 4, "EventMetadataEntry"),
        ("stat_metadata", 5, "StatMetadataEntry"))
    msg("XSpace", ("planes", 1, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.XSpace"))


def _op_name(meta, stat_names: Dict[int, str]) -> str:
    for st in meta.stats:
        if stat_names.get(st.metadata_id) == OP_NAME_STAT:
            return st.str_value
    return ""


def load_events(path: str) -> Tuple[Dict[str, list], List[Tuple[int, int]]]:
    """From an ``.xplane.pb``: ``ops[chip] = [(op name, start, end)]`` and
    the sorted ``(start, end)`` of every ``device_step`` span, times in
    whole ns on the trace's clock (exact, where ``ProfileData``'s are
    floats that round absolute timestamps)."""
    space = xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops: Dict[str, list] = {}
    steps = []
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        device = plane.name.startswith(xplane.DEVICE_PREFIX)
        if device:
            evs = ops.setdefault(plane.name, [])
            op_names = {k: _op_name(m, names) for k, m in meta.items()}
        elif not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != xplane.OPS_LINE:
                continue
            for ev in line.events:
                s = line.timestamp_ns + ev.offset_ps // 1000
                e = line.timestamp_ns + (ev.offset_ps + ev.duration_ps) // 1000
                if device:
                    evs.append((op_names.get(ev.metadata_id, ""), s, e))
                elif (ev.metadata_id in meta and meta[ev.metadata_id].name
                      == xplane.WINDOW_SPAN):
                    steps.append((s, e))
    return ops, sorted(steps)


def scope_ms(ops: Dict[str, list], steps: List[Tuple[int, int]],
             window_steps: int, scope: str) -> Optional[float]:
    """Mean device ms per window step of the ops whose op name holds
    ``scope``, clipped to the window; None when no op carries it or the
    trace has no chip or no window."""
    if not ops or window_steps < 1 or len(steps) <= window_steps:
        return None
    w0, w1 = steps[0][1], steps[window_steps][1]
    total, carried = 0, False
    for evs in ops.values():
        for name, s, e in evs:
            if scope in name:
                carried = True
                total += max(0, min(e, w1) - max(s, w0))
    if not carried:
        return None
    return total / len(ops) / window_steps / 1e6


def read(run, scope: str) -> Optional[float]:
    """``scope_ms`` of a traced run, from the trace it left in the
    harness's run directory."""
    from benchlib import harness

    if run.trace is None:
        return None
    path = xplane.find_xplane(os.path.join(harness.RUN_DIR, "trace"))
    if path is None:
        return None
    return scope_ms(*load_events(path), len(run.window_steps), scope)
