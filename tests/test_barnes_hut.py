"""Barnes-Hut t-SNE: ladder-vs-exact force parity and end-to-end embedding.

Parity target: plot/BarnesHutTsne.java:65 + clustering/sptree/SpTree.java
(computeNonEdgeForces / computeEdgeForces). The grid-ladder repulsion must
match the exact O(N^2) forces to BH-class accuracy, and the full pipeline
must separate clusters like the exact implementation does.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.plot import BarnesHutTsne, Tsne
from deeplearning4j_tpu.plot.barnes_hut import (
    _bh_repulsion,
    _knn,
    _ladder_config,
    _perplexity_search,
    build_sparse_p,
)


def _ladder_repulsion(y, theta):
    """The ladder as make_bh_step runs it: ONE jitted program per (N, theta)
    — called eagerly every jnp op of every level is its own compile."""
    R, l0, L = _ladder_config(y.shape[0], theta)
    return jax.jit(partial(_bh_repulsion, R=R, l0=l0, L=L))(y)


class TestLadderRepulsion:
    def _exact(self, yn):
        d2 = ((yn[:, None, :] - yn[None, :, :]) ** 2).sum(-1)
        num = 1.0 / (1.0 + d2)
        np.fill_diagonal(num, 0.0)
        rep = ((num ** 2)[..., None]
               * (yn[:, None, :] - yn[None, :, :])).sum(1)
        return rep, num.sum(1)

    def test_matches_exact_forces(self):
        rs = np.random.RandomState(0)
        y = jnp.asarray(rs.randn(800, 2) * 5, jnp.float32)
        rep, z = _ladder_repulsion(y, 0.5)
        rep_ex, z_ex = self._exact(np.asarray(y))
        # Z within ~2%, forces within ~5% of the mean force magnitude —
        # the BH accuracy class at theta=0.5
        np.testing.assert_allclose(np.asarray(z), z_ex, rtol=0.02)
        fmag = np.linalg.norm(rep_ex, axis=1).mean()
        err = np.linalg.norm(np.asarray(rep) - rep_ex, axis=1) / fmag
        assert err.mean() < 0.05, err.mean()

    def test_smaller_theta_is_more_accurate(self):
        rs = np.random.RandomState(1)
        y = jnp.asarray(rs.randn(600, 2) * 3, jnp.float32)
        rep_ex, z_ex = self._exact(np.asarray(y))

        def mean_err(theta):
            rep, _ = _ladder_repulsion(y, theta)
            fmag = np.linalg.norm(rep_ex, axis=1).mean()
            return (np.linalg.norm(np.asarray(rep) - rep_ex, axis=1)
                    / fmag).mean()

        assert mean_err(0.3) <= mean_err(1.0) + 1e-6


class TestSparseP:
    def test_knn_finds_true_neighbors(self):
        rs = np.random.RandomState(2)
        x = rs.randn(200, 5).astype(np.float32)
        idx, d2 = _knn(x, 10)
        d_full = ((x[:, None, :] - x[None, :, :]) ** 2).sum(-1)
        np.fill_diagonal(d_full, np.inf)
        expect = np.sort(d_full, axis=1)[:, :10]
        np.testing.assert_allclose(np.sort(d2, axis=1), expect, rtol=1e-4,
                                   atol=1e-4)

    def test_perplexity_entropy_hits_target(self):
        rs = np.random.RandomState(3)
        d2 = np.abs(rs.randn(50, 30)) * 3
        p = _perplexity_search(d2, 10.0)
        h = -np.sum(p * np.log(np.maximum(p, 1e-12)), axis=1)
        np.testing.assert_allclose(np.exp(h), 10.0, rtol=0.05)

    def test_edges_sum_to_one_and_symmetric(self):
        rs = np.random.RandomState(4)
        x = rs.randn(120, 8).astype(np.float32)
        ei, ej, ep = build_sparse_p(x, 15.0)
        np.testing.assert_allclose(ep.sum(), 1.0, rtol=1e-6)
        dense = np.zeros((120, 120))
        np.add.at(dense, (ei, ej), ep)
        np.testing.assert_allclose(dense, dense.T, atol=1e-9)


class TestEndToEnd:
    def test_bh_separates_clusters(self):
        rs = np.random.RandomState(5)
        a = rs.randn(150, 10) * 0.3
        b = rs.randn(150, 10) * 0.3 + 5.0
        x = np.concatenate([a, b])
        tsne = BarnesHutTsne(perplexity=15, theta=0.5, max_iter=300,
                             learning_rate=100.0, seed=0)
        y = tsne.fit(x)
        assert y.shape == (300, 2)
        assert np.isfinite(tsne.kl)
        ca, cb = y[:150].mean(0), y[150:].mean(0)
        intra = max(np.linalg.norm(y[:150] - ca, axis=1).mean(),
                    np.linalg.norm(y[150:] - cb, axis=1).mean())
        assert np.linalg.norm(ca - cb) > 2 * intra

    def test_bh_embedding_close_to_exact_quality(self):
        """Same data through exact Tsne and BH: both must reach comparable
        sparse-KL / separation — BH is an approximation of the same
        objective, not a different algorithm."""
        rs = np.random.RandomState(6)
        a = rs.randn(100, 6) * 0.4
        b = rs.randn(100, 6) * 0.4 + 4.0
        x = np.concatenate([a, b])
        kw = dict(perplexity=12, max_iter=250, learning_rate=100.0, seed=0)
        y_bh = BarnesHutTsne(theta=0.5, **kw).fit(x)

        def sep(y):
            ca, cb = y[:100].mean(0), y[100:].mean(0)
            intra = max(np.linalg.norm(y[:100] - ca, axis=1).mean(),
                        np.linalg.norm(y[100:] - cb, axis=1).mean())
            return np.linalg.norm(ca - cb) / intra

        y_ex = Tsne(num_dimension=2, **kw).fit(x)
        assert sep(y_bh) > 2.0
        assert sep(y_ex) > 2.0
