"""The fused gather-and-overlay kernel's share of its roofline: the least
time its HBM bytes need at the chip's peak bandwidth, over its device time
in the trace.  Bytes per call come from the padded shapes of every batch
the traced call finalized (``benchlib.counts.fused_gather_bytes``)."""
from benchlib import counts, peaks


def read(run):
    tr = run.trace
    if not tr or not tr["kernels"].get("fused_gather"):
        return None
    k = tr["kernels"]["fused_gather"]
    if k["calls"] != len(run.census):
        return None
    width = -(-run.config["feat_dim"] // 128) * 128
    total = sum(counts.fused_gather_bytes(c["n_pad"], c["n_ids"], width)
                for c in run.census)
    bw = peaks.peak(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (total / bw) / k["device_s"]
