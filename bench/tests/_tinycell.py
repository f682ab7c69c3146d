"""A benchmark cell cut to a test's size: the cell's own model, widths,
traffic and limits, on a 20k-vertex graph with batch 64 and its hop count
kept, fan-outs cut to 5, 3, 2 for the first three hops.  ``CELLS`` are
BENCHMARK.json's workloads, in its order."""
import json
import os

from _benchpath import BENCH, ROOT


def _bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS = tuple(w["name"] for w in _bench()["workloads"])
CUT_FANOUTS = (5, 3, 2)


def cut_fanouts(fanouts) -> list:
    """The configuration's fan-outs cut to a test's size, one per hop (a
    hop past the third is cut as the third)."""
    return [min(f, CUT_FANOUTS[min(i, len(CUT_FANOUTS) - 1)])
            for i, f in enumerate(fanouts)]


def tiny_cell(name: str, tmp_path, monkeypatch):
    from benchlib import harness

    monkeypatch.setattr(harness, "DATA_DIR", str(tmp_path / "data"))
    monkeypatch.setattr(harness, "RUN_DIR", str(tmp_path / "run"))
    bench = _bench()
    wl = {w["name"]: w for w in bench["workloads"]}[name]
    with open(os.path.join(BENCH, "configs", f"{wl['config']}.json")) as f:
        cfg = json.load(f)
    cfg.update(n_vertices=20_000, batch_size=64,
               fanouts=cut_fanouts(cfg["fanouts"]),
               cache_bytes_per_chip=2e6, bucket=256, train_fraction=0.05)
    return harness.Cell(name, bench=bench, config=cfg)
