"""Neighbor sampling validity: host and device samplers agree on semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep: deterministic fallback replays
    from _hyp_compat import given, settings, strategies as st

from repro.graph.csr import powerlaw_graph
from repro.graph.sampling import (dedup_levels, device_sample,
                                  host_sample_batch, unique_vertices)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 500))
def test_host_sampled_are_neighbors(seed):
    g = powerlaw_graph(500, 6, seed=1, feat_dim=8)
    rng = np.random.default_rng(seed)
    seeds = rng.integers(0, g.n, size=32)
    levels = host_sample_batch(g, seeds, (5, 3), rng)
    assert levels[1].shape == (32, 5) and levels[2].shape == (32, 5, 3)
    for b in range(8):
        nb = set(g.neighbors(seeds[b]).tolist())
        deg = len(g.neighbors(seeds[b]))
        for u in levels[1][b]:
            assert (u == -1 and deg == 0) or int(u) in nb


def test_device_sampler_valid():
    g = powerlaw_graph(400, 6, seed=2, feat_dim=8)
    indptr, indices = jnp.asarray(g.indptr), jnp.asarray(g.indices)
    seeds = jnp.arange(0, 64, dtype=jnp.int32)
    levels = device_sample(indptr, indices, seeds, (4, 2), jax.random.PRNGKey(0))
    l1 = np.asarray(levels[1])
    for b in range(16):
        nb = set(g.neighbors(b).tolist())
        for u in l1[b]:
            assert (u == -1 and len(nb) == 0) or int(u) in nb


def test_unique_vertices_drops_padding():
    levels = [np.array([1, 2]), np.array([[3, -1], [1, 2]])]
    assert unique_vertices(levels).tolist() == [1, 2, 3]


N_VERT = 1000
SHAPES = ((16,), (16, 5), (16, 5, 3))


def _dedup_oracle(levels):
    """The sort-and-search dedup ``dedup_levels`` replaces."""
    ids = unique_vertices(levels)
    pos = [np.clip(np.searchsorted(ids, np.maximum(lvl, 0)), 0,
                   max(len(ids) - 1, 0)) for lvl in levels]
    return ids, pos


def _assert_matches_oracle(levels, slot):
    ids, pos = dedup_levels(levels, slot)
    want_ids, want_pos = _dedup_oracle(levels)
    assert ids.dtype == want_ids.dtype == np.int64
    np.testing.assert_array_equal(ids, want_ids)
    assert len(pos) == len(levels)
    for lvl, p, w in zip(levels, pos, want_pos):
        assert p.dtype == w.dtype and p.shape == lvl.shape
        valid = lvl >= 0
        np.testing.assert_array_equal(p[valid], w[valid])
        np.testing.assert_array_equal(ids[p[valid]], lvl[valid])
        assert (p[~valid] == 0).all()


def _levels(rng, lo=0, hi=N_VERT, pad=0.2, shapes=SHAPES):
    out = []
    for shape in shapes:
        lvl = rng.integers(lo, hi, size=shape).astype(np.int64)
        lvl[rng.random(shape) < pad] = -1
        out.append(lvl)
    return out


def _case(name):
    rng = np.random.default_rng(7)
    if name == "random_padded":
        return _levels(rng)
    if name == "all_padding":
        return [np.full(s, -1, np.int64) for s in SHAPES]
    if name == "zero_absent":
        return _levels(rng, lo=1)
    if name == "zero_present":
        levels = _levels(rng, lo=1)
        levels[2][3, 1, 2] = 0
        levels[1][0, 0] = 0
        return levels
    if name == "last_vertex_present":
        levels = _levels(rng)
        levels[0][5] = N_VERT - 1
        return levels
    assert name == "single_level"
    return _levels(rng, shapes=((64,),))


@pytest.mark.parametrize("case", ["random_padded", "all_padding",
                                  "zero_absent", "zero_present",
                                  "last_vertex_present", "single_level"])
def test_dedup_levels_matches_sort_and_search(case):
    """Slot-map dedup == np.unique + searchsorted, padding positions 0."""
    _assert_matches_oracle(_case(case), np.empty(N_VERT, np.int32))


@pytest.mark.parametrize("fill", ["empty", "poisoned"])
def test_dedup_levels_reuses_slot_map_without_reset(fill):
    """One slot map across calls: stale entries from earlier batches are
    never read, whether the next id set overlaps, is disjoint or is a
    subset."""
    rng = np.random.default_rng(3)
    slot = np.empty(N_VERT, np.int32)
    if fill == "poisoned":
        slot[:] = rng.integers(-2**31, 2**31 - 1, size=N_VERT)
    for lo, hi in ((0, 600),      # first batch
                   (400, 1000),   # overlaps the first
                   (0, 300),      # disjoint from the second
                   (500, 700),    # subset of the second
                   (0, 1000)):    # superset of all
        _assert_matches_oracle(_levels(rng, lo, hi), slot)
