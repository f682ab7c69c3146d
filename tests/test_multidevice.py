"""Multi-device integration checks.

Spawned as a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest process keeps seeing 1 device (smoke tests must not see a
fake mesh).  Verifies on a real 2x2 mesh:

  * dist_decode_attention (seq-sharded KV + LSE combine) == local attention
  * shard_map MoE dispatch == single-device dispatch
  * int8 error-feedback compressed all-reduce ~= exact mean
  * sharded GNN DP train step == single-device step
"""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, sys.argv[1])
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.models import layers, moe
    from repro.models.sharding import Distribution
    from repro.configs.base import ModelConfig

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         devices=jax.devices()[:4],
                         axis_types=(AxisType.Auto,) * 2)
    dist = Distribution(mesh=mesh)
    key = jax.random.PRNGKey(0)

    # 1) dist decode attention == local
    B, Smax, Hq, Hkv, Dh = 4, 32, 8, 2, 16
    q = jax.random.normal(key, (B, 1, Hq, Dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Smax, Hkv, Dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Smax, Hkv, Dh))
    idx = jnp.arange(Smax)
    kpos = jnp.where(idx <= 20, idx, -1)
    with jax.set_mesh(mesh):
        o1 = layers.dist_decode_attention(q, k, v, jnp.array([20]), kpos, dist=dist)
    o2 = layers.decode_attention(q, k, v, jnp.array([20]), kpos)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-4, atol=2e-4)
    print("dist_decode OK")

    # 2) MoE shard_map dispatch == single-device (generous capacity)
    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                      n_experts=4, top_k=2, capacity_factor=8.0)
    p = {"router": jax.random.normal(key, (32, 4)) * 0.1,
         "w_gate": jax.random.normal(key, (4, 32, 64)) * 0.1,
         "w_up": jax.random.normal(key, (4, 32, 64)) * 0.1,
         "w_down": jax.random.normal(key, (4, 64, 32)) * 0.1}
    x = jax.random.normal(key, (4, 16, 32))
    o_local, _ = moe.moe_block(cfg, p, x, dist=Distribution.single_device(),
                               mode="train")
    with jax.set_mesh(mesh):
        o_dist, _ = moe.moe_block(cfg, p, x, dist=dist, mode="train")
    np.testing.assert_allclose(np.asarray(o_local), np.asarray(o_dist),
                               rtol=1e-4, atol=1e-4)
    print("moe dispatch OK")

    # 3) compressed all-reduce ~= exact mean (+EF shrinks the residual)
    from repro.train.compression import compressed_psum_mean
    import functools
    def body(x, ef):
        return compressed_psum_mean(x, ef, "data")
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data")), check_vma=False)
    xs = jax.random.normal(key, (8, 64))
    efs = jnp.zeros((8, 64))
    mean, ef2 = fn(xs, efs)
    exact = jnp.tile(xs.reshape(2, 4, 64).mean(0), (2, 1))
    err = np.abs(np.asarray(mean) - np.asarray(exact)).max()
    scale = float(jnp.abs(xs).max()) / 127
    assert err <= 2 * scale + 1e-6, (err, scale)
    print("compression OK")

    # 4) sharded GNN step == single device
    from repro.models.gnn import GNNConfig, defs as gdefs, loss_fn as gloss
    from repro.models.params import init_from_defs
    gcfg = GNNConfig(feat_dim=16, hidden=32, batch_size=8, fanouts=(4, 2))
    params = init_from_defs(gdefs(gcfg), key)
    batch = {
        "feats_0": jax.random.normal(key, (8, 16)),
        "feats_1": jax.random.normal(key, (8, 4, 16)),
        "feats_2": jax.random.normal(key, (8, 4, 2, 16)),
        "mask_1": jnp.ones((8, 4), bool),
        "mask_2": jnp.ones((8, 4, 2), bool),
        "labels": jax.random.randint(key, (8,), 0, 32),
    }
    l_single, _ = gloss(gcfg, params, batch)
    with jax.set_mesh(mesh):
        sb = jax.device_put(batch, jax.NamedSharding(mesh, P("data")))
        l_shard, _ = jax.jit(lambda p, b: gloss(gcfg, p, b))(params, sb)
    np.testing.assert_allclose(float(l_single), float(l_shard), rtol=1e-5)
    print("gnn dp OK")

    # 5) shard_map embedding lookup == plain take (vocab-sharded table)
    import dataclasses
    from repro.models import transformer as T
    from repro.configs import get_config
    cfg5 = dataclasses.replace(get_config("gemma3-1b", smoke=True),
                               embed_gather="shard_map")
    V, D = cfg5.padded_vocab, cfg5.d_model
    table = jax.random.normal(key, (V, D))
    toks = jax.random.randint(key, (4, 8), 0, cfg5.vocab_size)
    with jax.set_mesh(mesh):
        tab_sh = jax.device_put(table, jax.NamedSharding(mesh, P("model", None)))
        out_sm = T.embed_tokens(cfg5, {"embed": tab_sh}, toks, dist)
    out_ref = jnp.take(table, toks, axis=0).astype(jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(out_sm, np.float32),
                               np.asarray(out_ref, np.float32), rtol=1e-2, atol=1e-2)
    print("sharded embed OK")

    # 6) checkpoint restore onto a sharded template (elastic restart)
    import tempfile
    from repro.train.checkpoint import restore_checkpoint, save_checkpoint
    tree = {"w": jax.random.normal(key, (8, 64))}
    with tempfile.TemporaryDirectory() as d:
        path = save_checkpoint(d, 3, tree)
        like = {"w": jax.ShapeDtypeStruct(
            (8, 64), jnp.float32,
            sharding=jax.NamedSharding(mesh, P("data", "model")))}
        step, out = restore_checkpoint(path, like)
        assert step == 3
        np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(tree["w"]))
        assert out["w"].sharding.spec == P("data", "model")
    print("sharded restore OK")

    # 7) compressed-DP GNN training end to end on the mesh
    from repro.core.cliques import topology_matrix
    from repro.core.planner import build_plan
    from repro.graph.csr import powerlaw_graph
    from repro.train.loop import train_gnn
    g7 = powerlaw_graph(3000, 8, seed=9, feat_dim=16)
    plan7 = build_plan(g7, topology_matrix("nv2"), mem_per_device=500_000,
                       batch_size=256, seed=0)
    res = train_gnn(g7, plan7, GNNConfig(feat_dim=16, hidden=32,
                                         batch_size=64, fanouts=(4, 2),
                                         lr=3e-3),
                    steps=12, mesh=mesh, compress_grads=True)
    assert np.isfinite(res.losses).all()
    assert res.losses[-1] < res.losses[0] + 0.1
    print("compressed-DP training OK")
    print("ALL MULTIDEVICE OK")
""")


def test_multidevice_suite(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = tmp_path / "multidev.py"
    script.write_text(SCRIPT)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(script), src], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ALL MULTIDEVICE OK" in r.stdout
