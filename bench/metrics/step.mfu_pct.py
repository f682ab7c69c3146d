"""The whole step's share of the chip's bf16 peak: the GNN's forward and
backward FLOPs per step (``benchlib.counts.step_flops``) times steps per
second of the window."""
from benchlib import counts, peaks, refgnn


def read(run):
    cfg = run.config
    flops = counts.step_flops(refgnn.load_model(cfg["model"]),
                              cfg["batch_size"], cfg["fanouts"],
                              cfg["feat_dim"], cfg["hidden"], cfg["n_classes"],
                              cfg.get("model_args"))
    t0, t1 = run.window_ns
    steps_per_s = len(run.window_steps) / ((t1 - t0) / 1e9)
    return 100.0 * flops * steps_per_s / peaks.peak(run.device_kind)["bf16_flops"]
