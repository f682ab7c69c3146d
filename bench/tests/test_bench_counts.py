"""Operation and byte counts against hand arithmetic at tiny shapes, and
the peak table's refusal of unknown devices."""
import _benchpath  # noqa: F401
import pytest

from benchlib import counts, peaks, refgnn


def test_level_rows():
    assert counts.level_rows(4, (3, 2)) == [4, 12, 24]


def test_sage_step_flops_by_hand():
    # B=2, fan-outs (3, 2), D=4, H=5, C=6
    B, D, H, C = 2, 4, 5, 6
    # layer 0 on level 0 (2 rows) and level 1 (6 rows): two D x H products
    # per row, plus the masked mean over the level below (6*D and 12*D)
    l0_fwd = 2 * (2 * 2 * D * H) + 2 * 6 * D + 2 * (2 * 6 * D * H) + 2 * 12 * D
    l0_bwd = 2 * (2 * 2 * D * H) + 2 * (2 * 6 * D * H)  # weight grads only
    # layer 1 on level 0 (2 rows), aggregating 6 rows of width H
    l1_fwd = 2 * (2 * 2 * H * H) + 2 * 6 * H
    l1_bwd = 2 * (2 * 2 * H * H) + (2 * (2 * 2 * H * H) + 2 * 6 * H)
    head = 2 * B * H * C
    want = l0_fwd + l0_bwd + l1_fwd + l1_bwd + head + 2 * head
    got = counts.step_flops(refgnn.load_model("sage"), B, (3, 2), D, H, C)
    assert got == want


def test_gcn_step_flops_by_hand():
    B, D, H, C = 2, 4, 5, 6
    l0_fwd = 2 * 2 * D * H + 2 * 6 * D + 2 * 6 * D * H + 2 * 12 * D
    l0_bwd = 2 * 2 * D * H + 2 * 6 * D * H
    l1_fwd = 2 * 2 * H * H + 2 * 6 * H
    l1_bwd = 2 * 2 * H * H + (2 * 2 * H * H + 2 * 6 * H)
    head = 2 * B * H * C
    want = l0_fwd + l0_bwd + l1_fwd + l1_bwd + 3 * head
    assert counts.step_flops(refgnn.load_model("gcn"), B, (3, 2), D, H,
                             C) == want


def test_fused_gather_bytes_by_hand():
    # 1024 output rows of which 1000 real, 128 lanes of float32: 1000 rows
    # read, 1024 written, two int32 maps of 1024 entries
    assert counts.fused_gather_bytes(1024, 1000, 128) == \
        (1000 + 1024) * 128 * 4 + 2 * 1024 * 4


def test_peaks_known_and_unknown():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")
