"""The generator's statistics, the reference's step counts and the
traffic, by hand."""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cells, check, traffic
from objectives import logreg, logreg_reference

SMALL = {"n": 400, "p": 3000, "nnz_per_row": 30, "l2": 1e-4}


@pytest.fixture(scope="module")
def data():
    return logreg.generate(SMALL, 2**33 + 17)


def test_generator_shapes_nonzeros_and_norms(data):
    X, y = (np.asarray(a) for a in data)
    assert X.shape == (400, 3000) and X.dtype == np.float32
    assert y.shape == (400,) and set(np.unique(y)) == {-1.0, 1.0}
    assert (np.count_nonzero(X, axis=1) == 30).all()
    np.testing.assert_allclose(np.linalg.norm(X, axis=1), 1.0, rtol=1e-5)
    # the +0.3 skew makes about 62% of the values positive
    vals = X[X != 0]
    assert 0.57 < np.mean(vals > 0) < 0.67


def test_generator_labels_follow_a_separator_with_flips():
    X, y = (np.asarray(a) for a in logreg.generate(
        {"n": 4000, "p": 100, "nnz_per_row": 10}, 11))
    # 8% flipped labels: a linear fit on 40 rows per feature separates
    # most of the rows, far from the ~55% that unrelated labels would give
    w, *_ = np.linalg.lstsq(X, y, rcond=None)
    assert np.mean(np.sign(X @ w) == y) > 0.8


def test_generator_is_a_function_of_the_seed():
    a = logreg.generate(SMALL, 5)
    b = logreg.generate(SMALL, 5)
    c = logreg.generate(SMALL, 5 + 2**32)
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    assert not np.array_equal(a[0], c[0])


def test_reference_resolves_the_papers_step_counts():
    n = 20242
    assert logreg_reference.resolve(
        {"algo": "asysvrg", "scheme": "unlock", "num_threads": 10,
         "tau": 9}, n) == (40480, 9, "unlock")
    assert logreg_reference.resolve(
        {"algo": "svrg", "num_threads": 1}, n) == (40484, 0, "consistent")
    assert logreg_reference.resolve(
        {"algo": "hogwild", "scheme": "unlock", "num_threads": 10,
         "tau": 9}, n) == (20240, 9, "unlock")


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  cells.load_benchmark()["workloads"]])
def test_traffic_is_a_function_of_the_seed(cell):
    mix = cells.load_cell(cell).mix
    ts = traffic.tenants(mix)
    one = [traffic.request_rows(t, i, k, 2**31 + 3)
           for i, t in enumerate(ts) for k in range(-1, 3)]
    two = [traffic.request_rows(t, i, k, 2**31 + 3)
           for i, t in enumerate(ts) for k in range(-1, 3)]
    other = [traffic.request_rows(t, i, k, 2**31 + 4)
             for i, t in enumerate(ts) for k in range(-1, 3)]
    assert one == two and one != other
    seeds = [r["seed"] for req in one for r in req]
    assert len(set(seeds)) == len(seeds)
    # every seed gives the same sizes: the fields that shape the work
    shape = [(r["algo"], r.get("num_threads"), r.get("tau"))
             for req in one for r in req]
    assert shape == [(r["algo"], r.get("num_threads"), r.get("tau"))
                     for req in other for r in req]


def test_reference_matches_itself_in_float32_only():
    X, y = logreg.generate({"n": 64, "p": 128, "nnz_per_row": 8}, 3)
    row = {"algo": "asysvrg", "scheme": "inconsistent", "step_size": 1.0,
           "num_threads": 4, "tau": 3, "seed": 9}
    w32, l32 = logreg_reference.run_request(X, y, 1e-4, row, 2, 0.02)
    w16, l16 = logreg_reference.run_request(X, y, 1e-4, row, 2, 0.02,
                                            jnp.bfloat16)
    assert np.isfinite(w32).all() and l32[-1] < l32[0]
    assert np.max(np.abs(w16 - w32)) > 1e-3 * np.max(np.abs(w32))


def test_the_check_samples_every_kind_of_request():
    # four requests of one kind, one of another that differs only in the
    # read scheme of its second row: both kinds are checked on every seed
    def req(schemes):
        return {"rows": [{"algo": "asysvrg", "scheme": s} for s in schemes]}
    done = [req(["unlock", "unlock"])] * 4 + [req(["unlock", "consistent"])]
    for seed in range(2**31, 2**31 + 20):
        picked = check.sample(done, 1, seed)
        assert sorted(map(check.kind, picked)) == sorted(
            {check.kind(r) for r in done})
