"""A benchmark cell cut to a test's size: the cell's own model, widths,
traffic and limits, on a 20k-vertex graph with batch 64 and fan-outs 5x3."""
import json
import os

from _benchpath import BENCH, ROOT

CELLS = ("sage-papers100m.overflow", "gcn-products.resident")


def tiny_cell(name: str, tmp_path, monkeypatch):
    from benchlib import harness

    monkeypatch.setattr(harness, "DATA_DIR", str(tmp_path / "data"))
    monkeypatch.setattr(harness, "RUN_DIR", str(tmp_path / "run"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    wl = {w["name"]: w for w in bench["workloads"]}[name]
    with open(os.path.join(BENCH, "configs", f"{wl['config']}.json")) as f:
        cfg = json.load(f)
    cfg.update(n_vertices=20_000, batch_size=64, fanouts=[5, 3],
               cache_bytes_per_chip=2e6, bucket=256, train_fraction=0.05)
    return harness.Cell(name, bench=bench, config=cfg)
