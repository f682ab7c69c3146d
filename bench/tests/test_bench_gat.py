"""The GAT cell's benchmark files: the reference layer module against a
float64 computation by hand and against the program, the step's FLOP
count worked out by hand, and the reader of the program's named scopes
on synthetic traces."""
import json
import os

import _benchpath  # noqa: F401
import numpy as np
import pytest
from _benchpath import BENCH

from benchlib import counts, refgnn, scopes

ARGS = {"n_heads": 2}
GAT = refgnn.load_model("gat")


def _batch(B=3, fanouts=(3, 2), D=5, seed=0):
    rng = np.random.default_rng(seed)
    feats, masks, shape = [], [], (B,)
    for li in range(len(fanouts) + 1):
        feats.append(rng.normal(size=shape + (D,)).astype(np.float32))
        masks.append(rng.random(shape) < 0.6)
        if li < len(fanouts):
            shape = shape + (fanouts[li],)
    masks[1][0] = False  # a seed with no valid neighbour
    return feats, masks


def _params(shapes, seed):
    """The reference's initial parameters with the zero biases made random."""
    import jax

    params = refgnn.init_params(shapes, seed)
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda x: x + 0.3 * rng.normal(size=x.shape).astype(np.float32),
        params)


def hand_level(p, last, heads, slope, h_self, h_neigh, mask):
    """One level of the GAT layer row by row and head by head, float64."""
    w, ws = np.float64(p["w"]), np.float64(p["w_skip"])
    a_s, a_d = np.float64(p["att_src"]), np.float64(p["att_dst"])
    d = w.shape[0]
    hs = np.float64(h_self).reshape(-1, d)
    hn = np.float64(h_neigh).reshape(len(hs), -1, d)
    mk = np.asarray(mask).reshape(len(hs), -1)
    out = []
    for r in range(len(hs)):
        zs = (hs[r] @ w).reshape(heads, -1)
        zj = (hn[r] @ w).reshape(len(mk[r]), heads, -1)[mk[r]]
        heads_out = []
        for h in range(heads):
            cand = np.concatenate([zs[h][None], zj[:, h]])
            e = cand @ a_s[:, h] + zs[h] @ a_d[:, h]
            e = np.where(e > 0, e, slope * e)
            wt = np.exp(e - e.max())
            heads_out.append((wt / wt.sum()) @ cand)
        x = np.float64(p["b"]) + hs[r] @ ws + np.float64(p["b_skip"])
        if last:
            out.append(np.mean(heads_out, axis=0) + x)
        else:
            x = np.concatenate(heads_out) + x
            out.append(np.where(x > 0, x, np.expm1(np.minimum(x, 0))))
    return np.array(out).reshape(np.shape(h_self)[:-1] + (-1,))


def test_reference_forward_by_hand():
    import jax.numpy as jnp

    feats, masks = _batch()
    shapes = refgnn.param_shapes(GAT, 5, 4, 6, 2, ARGS)
    params = _params(shapes, 7)
    got = np.asarray(refgnn.forward(
        GAT, params, [jnp.asarray(f) for f in feats],
        [jnp.asarray(m) for m in masks], "highest", ARGS))
    h = list(feats)
    for li in range(2):
        p = {k: np.asarray(v) for k, v in params[f"layer{li}"].items()}
        h = [hand_level(p, li == 1, 2, 0.2, h[lev], h[lev + 1],
                        masks[lev + 1]) for lev in range(2 - li)]
    assert got.shape == (3, 6)
    np.testing.assert_allclose(got, h[0], rtol=1e-5, atol=1e-5)


def test_program_equals_reference():
    """Initial parameters, loss and gradients of the program's GAT equal
    the reference's bit for bit (on the CPU at the default precision)."""
    import jax
    import jax.numpy as jnp

    from repro.models import gnn
    from repro.models.params import init_from_defs

    B, fan, D, hid, nc = 8, (3, 2, 2), 7, 4, 5
    feats, masks = _batch(B, fan, D, seed=3)
    y = jnp.asarray(np.arange(B) % nc, jnp.int32)
    cfg = gnn.GNNConfig(model="gat", feat_dim=D, hidden=hid, n_classes=nc,
                        fanouts=fan, batch_size=B, **ARGS)
    shapes = refgnn.param_shapes(GAT, D, hid, nc, 3, ARGS)
    p_ref = refgnn.init_params(shapes, 2**31 + 5)
    p_prog = init_from_defs(gnn.defs(cfg), jax.random.PRNGKey(2**31 + 5))
    assert jax.tree.structure(p_ref) == jax.tree.structure(p_prog)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_prog)):
        assert np.array_equal(a, b)
    batch = {f"feats_{li}": jnp.asarray(f) for li, f in enumerate(feats)}
    batch.update({f"mask_{li}": jnp.asarray(m)
                  for li, m in enumerate(masks) if li})
    batch["labels"] = y
    lp, gp = jax.jit(jax.value_and_grad(
        lambda p: gnn.loss_fn(cfg, p, batch)[0]))(p_ref)
    lr, gr = jax.jit(jax.value_and_grad(lambda p: refgnn.loss(
        GAT, p, [batch[f"feats_{li}"] for li in range(4)],
        [jnp.asarray(m) for m in masks], y, "default", ARGS)))(p_ref)
    assert float(lp) == float(lr)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert np.array_equal(a, b)


def test_step_flops_of_gat_products():
    with open(os.path.join(BENCH, "configs", "gat-products.json")) as f:
        cfg = json.load(f)
    # rows per level: 512, 5120, 51200, 512000; 4 heads
    # layer 0 (100 -> 4 x 128 = 512, no input gradient) on levels 0..2:
    # R = 56832 own rows over Rb = 568320 rows below
    R, Rb = 56_832, 568_320
    proj = 2 * (R + Rb) * 100 * 512 + 2 * R * 100 * 512  # w on both, skip
    att = 2 * (Rb + R) * 512 + 2 * R * 512 + 2 * (Rb + R) * 512
    layer0 = (proj + att) + (proj + 2 * att)
    assert layer0 == 143_685_844_992
    # layer 1 (512 -> 512, input gradient) on levels 0..1
    R, Rb = 5_632, 56_320
    proj = 2 * (R + Rb) * 512 * 512 + 2 * R * 512 * 512
    att = 2 * (Rb + R) * 512 + 2 * R * 512 + 2 * (Rb + R) * 512
    layer1 = (proj + att) + (2 * proj + 2 * att)
    assert layer1 == 106_698_375_168
    # layer 2 (512 -> 4 heads of 47, averaged to 47) on level 0
    R, Rb = 512, 5_120
    proj = 2 * (R + Rb) * 512 * 188 + 2 * R * 512 * 47
    att = 2 * (Rb + R) * 188 + 2 * R * 188 + 2 * (Rb + R) * 188
    layer2 = (proj + att) + (2 * proj + 2 * att)
    assert layer2 == 3_339_890_688
    # no head
    assert counts.step_flops(GAT, cfg["batch_size"], cfg["fanouts"],
                             cfg["feat_dim"], cfg["hidden"],
                             cfg["n_classes"], cfg["model_args"]) == \
        layer0 + layer1 + layer2 == 253_724_110_848


# ---- the reader of the program's named scopes ---------------------------

FWD = "jit(train_step_plain)/jvp(gat_attention)/mul"
BWD = "jit(train_step_plain)/transpose(jvp(gat_attention))/mul"
PROJ = "jit(train_step_plain)/jvp(gat_project)/dot_general"
# device_step spans: the window opens at 100 and closes at 300 (2 steps)
STEPS = [(0, 100), (150, 200), (250, 300), (350, 400)]


def test_scope_counts_forward_and_backward_in_the_window():
    ops = {"/device:TPU:0": [
        (FWD, 110, 130),   # 20 inside
        (BWD, 140, 160),   # 20 inside
        (PROJ, 160, 190),  # another scope
        (FWD, 90, 120),    # 20 of its 30 inside: clipped at the open
        (BWD, 290, 320),   # 10 of its 30 inside: clipped at the close
        (FWD, 320, 340),   # after the window
        ("", 200, 240)]}   # no op name
    assert scopes.scope_ms(ops, STEPS, 2, "gat_attention") == \
        pytest.approx((20 + 20 + 20 + 10) / 2 / 1e6)
    assert scopes.scope_ms(ops, STEPS, 2, "gat_project") == \
        pytest.approx(30 / 2 / 1e6)
    # one window step: the window closes at 200
    assert scopes.scope_ms(ops, STEPS, 1, "gat_attention") == \
        pytest.approx((20 + 20 + 20) / 1e6)


def test_scope_averages_over_chips():
    ops = {"/device:TPU:0": [(FWD, 110, 150)],
           "/device:TPU:1": [(BWD, 110, 130)]}
    assert scopes.scope_ms(ops, STEPS, 2, "gat_attention") == \
        pytest.approx((40 + 20) / 2 / 2 / 1e6)


def test_scope_none_when_no_op_carries_it():
    ops = {"/device:TPU:0": [(PROJ, 110, 130), ("", 140, 150)]}
    assert scopes.scope_ms(ops, STEPS, 2, "gat_attention") is None
    assert scopes.scope_ms({}, STEPS, 2, "gat_project") is None
    assert scopes.scope_ms(ops, STEPS[:2], 2, "gat_project") is None


def _xspace(tmp_path):
    """A trace whose ops carry their op name (or another stat, or none)
    on their metadata, and a host line with three device_step spans."""
    space = scopes.xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "source")):
        dev.stat_metadata.add(key=key).value.name = name
    meta = {}
    for key in (10, 11, 12, 13):
        meta[key] = dev.event_metadata.add(key=key).value
        meta[key].name = f"%fusion.{key} = f32[8]"
    meta[10].stats.add(metadata_id=1, str_value=PROJ)
    meta[11].stats.add(metadata_id=2, str_value="gnn.py:120")
    meta[11].stats.add(metadata_id=1, str_value=BWD)
    meta[12].stats.add(metadata_id=1, str_value=FWD)
    meta[13].stats.add(metadata_id=2, str_value="not an op name")
    dev.lines.add(name="XLA Modules", timestamp_ns=1000).events.add(
        metadata_id=10, offset_ps=0, duration_ps=10**6)
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    ops.events.add(metadata_id=10, offset_ps=110_000, duration_ps=20_000)
    ops.events.add(metadata_id=11, offset_ps=140_000, duration_ps=20_000)
    ops.events.add(metadata_id=12, offset_ps=160_500, duration_ps=9_000)
    ops.events.add(metadata_id=13, offset_ps=170_000, duration_ps=5_000)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "device_step"
    host.event_metadata.add(key=2).value.name = "prefetch_get"
    line = host.lines.add(name="python", timestamp_ns=1000)
    for s, e in STEPS[:3]:
        line.events.add(metadata_id=1, offset_ps=s * 1000,
                        duration_ps=(e - s) * 1000)
    line.events.add(metadata_id=2, offset_ps=0, duration_ps=50_000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    return str(path)


def test_load_events_reads_the_op_name_stat(tmp_path):
    ops, steps = scopes.load_events(_xspace(tmp_path))
    assert ops == {"/device:TPU:0": [(PROJ, 1110, 1130), (BWD, 1140, 1160),
                                     (FWD, 1160, 1169), ("", 1170, 1175)]}
    assert steps == [(1000, 1100), (1150, 1200), (1250, 1300)]
    assert scopes.scope_ms(ops, steps, 2, "gat_attention") == \
        pytest.approx((20 + 9) / 2 / 1e6)


def test_load_events_times_as_profile_data(tmp_path):
    from jax.profiler import ProfileData

    from benchlib import xplane

    path = _xspace(tmp_path)
    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    ops, host = xplane.load_events(pd)
    got_ops, got_steps = scopes.load_events(path)
    assert [(s, e) for _, s, e in got_ops["/device:TPU:0"]] == \
        [(s, e) for _, s, e, _ in ops["/device:TPU:0"]]
    assert got_steps == sorted((s, e) for n, s, e, _ in host
                               if n == "device_step")
