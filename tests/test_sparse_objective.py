"""`EllLogisticRegression`: logistic regression over sparse (ELL) rows.

Checked at n = 64, p = 5,000, 8 nonzeros a row, on rows drawn by the
benchmark's generator (`benchmarks/chip/objectives/logreg_ell.py`):
against `LogisticRegression` on the same rows densified, for the bits of
a served sweep under any group width, for its runner key, against the
benchmark's plain reference, and for a generator that never densifies.
"""
import re
import sys
from pathlib import Path

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EllLogisticRegression, LogisticRegression, SweepSpec,
                        run_sweep)
from repro.service import SweepService, cache

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from objectives import logreg, logreg_ell, logreg_ell_reference  # noqa: E402

SMALL = {"n": 64, "p": 5000, "nnz_per_row": 8}
L2 = 1e-4
SCHEMES = ("consistent", "inconsistent", "unlock")


@pytest.fixture(scope="module")
def data():
    return logreg_ell.generate(SMALL, 2**33 + 5)


@pytest.fixture(scope="module")
def ell(data):
    return logreg_ell.program({"l2": L2}, data)


def _dense(rows, y):
    X = np.zeros((y.shape[0], rows.p), np.float32)
    X[np.arange(y.shape[0])[:, None], np.asarray(rows.cols)] = np.asarray(
        rows.vals)
    return LogisticRegression(X, y, L2)


def _spec(scheme, seed):
    return SweepSpec(scheme=scheme, seed=seed, step_size=1.0, tau=3,
                     num_threads=4, inner_steps=25)


# Each quantity sums the same float32 terms as the dense class in another
# grouping (a row's 8 nonzeros, not 5,000 coordinates with zeros; ||w||^2
# in blocks, not one scan), so they agree to a few float32 ulps of the
# result: rtol 1e-6 is about 8 ulps, and a term lost or doubled would move
# them by 1e-3 or more.
MATH = {
    "loss": lambda o, w: o.flat_loss(o.data_args(), w),
    "full_grad": lambda o, w: o.flat_full_grad(o.data_args(), w),
    "sample_grad": lambda o, w: o.flat_sample_grad(o.data_args(), 17, w),
}


@pytest.mark.parametrize("what", MATH)
def test_math_equals_the_dense_class_on_the_densified_rows(data, ell, what):
    w = 0.3 * np.random.default_rng(3).standard_normal(SMALL["p"]).astype(
        np.float32)
    got = np.asarray(MATH[what](ell, w))
    want = np.asarray(MATH[what](_dense(*data), w))
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * np.max(np.abs(want)))


def _coalesced(ell, specs):
    """Each row's (history, w) from a `SweepService` that took each row as
    a request of its own and pooled each scheme's rows in one group."""
    svc = SweepService(ell, epochs=2)
    rids = [svc.submit([spec]) for spec in specs]
    svc.flush()
    assert svc.stats().rows_coalesced > 0
    return [(svc.result(rid).histories[0], svc.result(rid).final_w[0])
            for rid in rids]


@pytest.mark.parametrize("path", ["run_sweep", "coalesced"])
def test_rows_bits_do_not_depend_on_the_group(ell, path):
    """Width 1 against width 2, a group for each read scheme, through
    `run_sweep` or through six requests the service coalesces."""
    specs = [_spec(s, 40 + k) for k, s in enumerate(SCHEMES * 2)]
    if path == "run_sweep":
        res = run_sweep(ell, 2, specs)
        grouped = list(zip(res.histories, res.final_w))
    else:
        grouped = _coalesced(ell, specs)
    for spec, (hist, w) in zip(specs, grouped):
        alone = run_sweep(ell, 2, [spec])
        np.testing.assert_array_equal(hist, alone.histories[0])
        np.testing.assert_array_equal(w, alone.final_w[0])


def test_objectives_that_differ_only_in_p_get_different_runners(data):
    rows, y = data
    a = EllLogisticRegression(rows.cols, rows.vals, y, rows.p, L2)
    b = EllLogisticRegression(rows.cols, rows.vals, y, rows.p + 1, L2)
    assert [x.shape for x in a.data_args()] == [x.shape
                                                for x in b.data_args()]
    cache.clear_cache()
    try:
        run_sweep(a, 1, [_spec("unlock", 1)])
        run_sweep(b, 1, [_spec("unlock", 1)])
        stats = cache.cache_stats()
        assert (stats.misses, stats.compiles, cache.cache_size()) == (2, 2, 2)
    finally:
        cache.clear_cache()


@pytest.mark.parametrize("scheme", SCHEMES)
def test_asysvrg_matches_the_plain_reference(data, ell, scheme):
    """The served row against the benchmark's reference over the same
    rows: the same random stream and ring, so the iterates differ by
    rounding alone (observed under 1e-6 of max |w|); 1e-4 leaves room for
    the summation orders and fails a stream or a reader out of step."""
    row = {"algo": "asysvrg", "scheme": scheme, "step_size": 1.0,
           "num_threads": 4, "tau": 3, "seed": 21}
    res = run_sweep(ell, 2, [SweepSpec(**row)], drop_prob=0.02)
    w_ref, l_ref = logreg_ell_reference.run_request(*data, L2, row, 2, 0.02)
    w = res.final_w[0]
    assert np.max(np.abs(w - w_ref)) <= 1e-4 * np.max(np.abs(w_ref))
    np.testing.assert_allclose(res.histories[0], l_ref, rtol=1e-4)


def test_runner_names_the_ell_scopes(ell):
    """The group runner's program carries both names the objective gives
    its device work, which a device trace reports per op."""
    from repro.core.objective import ELL_SCOPES
    from repro.core.sweep import _group_fn, _row_args, plan_sweep
    specs = [_spec("unlock", 1)]
    plan = plan_sweep(ell, 1, specs)
    (key, members), = plan.groups.items()
    engine = key.engine
    fn, _ = _group_fn(engine, obj=ell, num_data=len(ell.data_args()),
                      epochs=1, total=key.total, buf_len=key.buf_len,
                      option=key.option, drop_prob=0.02)
    args = _row_args(engine, plan.specs, plan.resolved, members,
                     ell.init_flat())
    text = jax.jit(fn).lower(*ell.data_args(), *args).as_text(
        debug_info=True)
    # the name stacks of the lowered module's locations; a scope opened
    # right inside a transform prints as vmap(ell_grad)
    parts = {re.sub(r"^[\w.]+\((.*)\)$", r"\1", part)
             for stack in re.findall(r'loc\("([^"<]*)"', text)
             for part in stack.split("/")}
    assert ELL_SCOPES == logreg_ell.SCOPES
    assert set(logreg_ell.SCOPES) <= parts


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its equations
    (scan and loop bodies, calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                inner = getattr(sub, "jaxpr", sub)
                if isinstance(inner, jax.extend.core.Jaxpr):
                    yield from _eqns(inner)


def _avals(jaxpr):
    """Every value's abstract array in a jaxpr and its nested jaxprs."""
    for eqn in _eqns(jaxpr):
        yield from (v.aval for v in (*eqn.invars, *eqn.outvars)
                    if hasattr(v.aval, "shape"))


def test_ell_grad_scope_holds_no_p_wide_work_but_its_scatters(ell):
    """`ell_grad` names the row's work alone. The p-wide l2·w takes in the
    read iterate, and on the chip XLA fuses the unlock read's draw and
    select into the op that computes it; inside the scope, that fusion
    would be timed as the objective's. So the only p-wide results in the
    scope are the scatters of the scaled rows."""
    from repro.core.objective import ELL_GRAD_SCOPE
    from repro.core.sweep import _group_fn, _row_args, plan_sweep
    plan = plan_sweep(ell, 1, [_spec("unlock", 1)])
    (key, members), = plan.groups.items()
    engine = key.engine
    fn, _ = _group_fn(engine, obj=ell, num_data=len(ell.data_args()),
                      epochs=1, total=key.total, buf_len=key.buf_len,
                      option=key.option, drop_prob=0.02)
    args = _row_args(engine, plan.specs, plan.resolved, members,
                     ell.init_flat())
    jaxpr = jax.make_jaxpr(fn)(*ell.data_args(), *args)
    scoped = [e for e in _eqns(jaxpr.jaxpr)
              if ELL_GRAD_SCOPE in str(e.source_info.name_stack).split("/")]
    wide = {e.primitive.name for e in scoped
            if any(ell.p in getattr(v.aval, "shape", ()) for v in e.outvars)}
    assert scoped and wide == {"scatter-add"}


def test_generate_never_builds_an_n_by_p_array():
    n, p = SMALL["n"], SMALL["p"]
    jaxpr = jax.make_jaxpr(lambda k: logreg_ell._generate(
        k, n=n, p=p, nnz=SMALL["nnz_per_row"]))(jax.random.PRNGKey(0))
    sizes = [int(np.prod(a.shape)) for a in _avals(jaxpr.jaxpr)]
    assert max(sizes) == p                       # the hidden separator
    assert all(s < n * p for s in sizes)


def test_generate_draws_the_nonzeros_of_the_dense_generator(data):
    """The same keys and statistics as `logreg`: the ELL rows are the
    nonzeros of the dense set of the same configuration and seed."""
    rows, y = data
    X, y_dense = (np.asarray(a) for a in logreg.generate(SMALL, 2**33 + 5))
    np.testing.assert_array_equal(_dense(rows, y).X, X)
    np.testing.assert_array_equal(np.asarray(y), y_dense)
    assert rows.cols.dtype == jnp.int32 and rows.p == SMALL["p"]
