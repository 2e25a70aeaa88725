"""The Kronecker generator and the PageRank loop at scale 10."""
import json

import numpy as np
import pytest

from bench import kronecker
from bench.reference import pagerank as ref
from conftest import REPO

GAP = json.loads((REPO / "bench" / "configs" / "gap-kron20.json").read_text())
RULE = {"damping": 0.85, "tol": 1e-4, "maxiter": 20}


def _gen(seed, scale=10, edges=10_000):
    return kronecker.generate(seed, scale=scale, edgefactor=GAP["edgefactor"],
                              initiator=GAP["initiator"], undirected_edges=edges)


def test_same_seed_same_graph_other_seed_other_graph():
    a, b = _gen(3000000001), _gen(3000000001)
    assert (a != b).nnz == 0 and a.nnz == b.nnz
    assert (a != _gen(3000000002)).nnz > 0
    # seeds that agree in their low 32 bits are different seeds
    assert (_gen(5) != _gen(5 + 2 ** 33)).nnz > 0


def test_every_seed_has_the_same_size():
    assert {_gen(s).nnz for s in (1, 2, 3, 3000000004)} == {20_000}
    with pytest.raises(ValueError, match="distinct edges"):
        _gen(1, edges=16 * 1024 * 9 // 8)  # more than the draws can give


def test_graph_is_symmetric_loop_free_and_unit():
    g = _gen(7)
    assert g.shape == (1024, 1024)
    assert (g != g.T).nnz == 0
    assert g.diagonal().sum() == 0
    assert np.all(g.data == 1.0)
    g.sum_duplicates()
    assert np.all(g.data == 1.0)  # no duplicate edges were stored
    deg = np.diff(g.indptr)  # a power law, with isolated vertices
    assert deg.max() > 20 * deg.mean() and (deg == 0).any()


@pytest.mark.parametrize("fmt,backend", [("coo", "plain"), ("coo", "pallas")])
def test_pagerank_loop_matches_scipy(fmt, backend):
    import jax

    from repro.core import as_operator
    from bench.drivers.pagerank import pagerank

    g = _gen(3000000003)
    A = as_operator(g, fmt).using(backend, fallback=False)
    inv = ref.inverse_degree(g).astype(np.float32)
    scores, err, iters = jax.jit(lambda A, inv: pagerank(A, inv, **RULE))(A, inv)
    answers, k = ref.pagerank(g, **RULE)
    assert abs(int(iters) - k) <= 1 and float(err) < RULE["tol"]
    assert ref.l1_gap(scores, answers) < 1e-6
