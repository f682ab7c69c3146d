"""The seeded chunked graph generator: deterministic from its seed, blind
to the thread count, and true to the profile's mean degree and skew."""
import _benchpath  # noqa: F401
import numpy as np
import pytest

from benchlib import graphgen


@pytest.fixture
def small_chunks(monkeypatch):
    # several chunks at a test's size, so chunking and threading are exercised
    monkeypatch.setattr(graphgen, "CHUNK", 1 << 14)
    monkeypatch.setattr(graphgen, "FEAT_CHUNK", 1 << 10)


def test_deterministic_from_seed_and_thread_count(small_chunks, monkeypatch):
    a = graphgen.powerlaw_csr(20_000, 14, 0.8, seed=5)
    monkeypatch.setattr(graphgen, "_threads", lambda: 1)
    b = graphgen.powerlaw_csr(20_000, 14, 0.8, seed=5)
    c = graphgen.powerlaw_csr(20_000, 14, 0.8, seed=6)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[1], c[1])


@pytest.mark.parametrize("n,deg", [(20_000, 14), (5_000, 50)])
def test_csr_is_valid_and_keeps_mean_degree(small_chunks, n, deg):
    indptr, indices = graphgen.powerlaw_csr(n, deg, 0.8, seed=1)
    assert indptr.dtype == np.int64 and indices.dtype == np.int32
    assert indptr[0] == 0 and indptr[-1] == len(indices)
    assert (np.diff(indptr) >= 0).all()
    assert indices.min() >= 0 and indices.max() < n
    src = np.repeat(np.arange(n), np.diff(indptr))
    assert not (src == indices).any()  # self-loops dropped
    # only the dropped self-loops take edges away
    assert deg * 0.98 <= len(indices) / n <= deg


def test_skew_matches_the_program_generator(small_chunks):
    """Same Chung-Lu model as repro.graph.csr.powerlaw_graph: the share of
    edge endpoints on the hottest 1% of vertices agrees within a few
    points."""
    from repro.graph.csr import powerlaw_graph

    n, deg = 20_000, 14
    indptr, indices = graphgen.powerlaw_csr(n, deg, 0.8, seed=3)
    ref = powerlaw_graph(n, deg, alpha=0.8, seed=3)

    def top_share(ip, ix):
        d_in = np.bincount(ix, minlength=n)
        d_out = np.diff(ip)
        k = n // 100
        return (np.sort(d_in)[-k:].sum() / len(ix),
                np.sort(d_out)[-k:].sum() / len(ix))

    ours, theirs = top_share(indptr, indices), top_share(ref.indptr,
                                                         ref.indices)
    assert abs(ours[0] - theirs[0]) < 0.03 and abs(ours[1] - theirs[1]) < 0.03


def test_features_and_split(small_chunks):
    x = graphgen.uniform_features(3000, 100, seed=9)
    assert x.dtype == np.float32 and x.shape == (3000, 100)
    assert np.array_equal(x, graphgen.uniform_features(3000, 100, seed=9))
    assert 0.0 <= x.min() and x.max() < 1.0
    assert abs(float(x.mean()) - 0.5) < 0.01
    tv = graphgen.train_split(100_000, 0.0109, seed=9)
    assert len(tv) == 1090 and (np.diff(tv) > 0).all()


def test_load_or_build_reuses_the_saved_graph(small_chunks, tmp_path):
    a = graphgen.load_or_build(str(tmp_path), 4000, 14, 0.8, 2)
    b = graphgen.load_or_build(str(tmp_path), 4000, 14, 0.8, 2)
    assert a[2] and not b[2]
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
