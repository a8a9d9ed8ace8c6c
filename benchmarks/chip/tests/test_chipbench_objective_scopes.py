"""The device trace read by the scope names an objective kind declares.

A plane worked out by hand, in the manner of `test_chipbench_scopes.py`:
two calls of the ELL snapshot (the second after two inner steps, whose
`svrg_update` calls cut the segments), each step with the two gathers and
scatters of ``ell_grad``. Times are nanoseconds on the trace's clock.
"""
from types import SimpleNamespace as NS

import pytest

from chipbench import cells, objective_scopes, readings, scopes, xplane
from objectives import logreg_ell

EPOCH = "jit(g)/vmap()/while/body/closed_call"
SNAP = EPOCH + "/snapshot/ell_snapshot"
STEP = EPOCH + "/while/body/closed_call/inner_step"
GRAD = STEP + "/sample_grad/ell_grad"

META = {
    "%gather.1": SNAP + "/gather:",
    "%while.3": "",                                # the row loop: no tf_op
    "%scatter.1": SNAP + "/while/body/scatter-add:",
    "%gather.2": "jit(g)/vmap(ell_grad)/gather:",  # a scope inside vmap
    "%scatter.2": GRAD + "/scatter-add:",
    "%fusion.7": STEP + "/sample_grad/mul:",
    "%svrg_update.1": STEP + "/svrg_update/svrg_update/pallas_call:",
}
OPS = [  # name, start, duration
    ("%gather.1", 1_000, 100), ("%while.3", 1_100, 500),
    ("%scatter.1", 1_150, 100), ("%scatter.1", 1_300, 100),
    ("%gather.2", 2_000, 50), ("%scatter.2", 2_050, 50),
    ("%fusion.7", 2_100, 100), ("%svrg_update.1", 2_200, 50),
    ("%gather.2", 3_000, 50), ("%scatter.2", 3_050, 50),
    ("%fusion.7", 3_100, 100), ("%svrg_update.1", 3_200, 50),
    ("%gather.1", 4_000, 100),
]


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


@pytest.fixture
def hand():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev(readings.SYNC_MARKER, 1_000, 10)])])
    device = NS(name="/device:TPU:0", lines=[
        NS(name=scopes.MODULES_LINE, events=[_ev("jit_g(1)", 900, 9_000)]),
        NS(name=readings.OPS_LINE, events=[_ev(*op) for op in OPS])])
    meta = {"/device:TPU:0": {k: xplane.OpMeta(tf_op=v)
                              for k, v in META.items()}}
    planes = [host, device]
    return (scopes.reduce_scopes(planes, meta, 1_000, 9_000, 1),
            objective_scopes.reduce_named(planes, meta, 1_000, 9_000, 1,
                                          logreg_ell.SCOPES))


def test_named_time_and_calls_by_hand(hand):
    epoch, named = hand
    assert epoch.inner_steps == 2
    # 100 + 100 of gathers, 200 of scatters, and the loop's own 500 - 200
    assert named.scope_s["ell_snapshot"] == pytest.approx(700e-9)
    assert named.scope_calls["ell_snapshot"] == 2
    # a gather and a scatter of 50 in each step; %fusion.7 is not in it
    assert named.scope_s["ell_grad"] == pytest.approx(200e-9)
    assert set(named.scope_s) == {"ell_snapshot", "ell_grad"}


CONFIG = {"n": 10, "p": 100, "nnz_per_row": 5}
HBM = 819e9


def _read(metric, hand, on_chip=False):
    epoch, named = hand
    r = NS(scopes=epoch, objective_scopes=named, trace=None, on_chip=on_chip,
           cell=NS(chips=1, config=CONFIG), peaks={"hbm_bytes_per_s": HBM})
    return cells.metric_reader(metric)(r)


def test_ell_metrics_by_hand(hand):
    assert _read("ell_grad_us", hand) == pytest.approx(0.1)
    # 10 x 5 ids and values of 4 bytes each, 10 labels, w read and the
    # gradient written: 1,240 bytes in 350 ns a call
    share = 100 * 1_240 / 350e-9 / HBM
    assert _read("ell_snapshot_hbm_share", hand) == pytest.approx(share)


@pytest.mark.parametrize("metric,scope", [
    ("ell_grad_us", "ell_grad"), ("ell_snapshot_hbm_share", "ell_snapshot")])
def test_on_a_tpu_a_trace_that_lacks_the_scope_is_an_error(hand, metric,
                                                           scope):
    epoch, named = hand
    lacking = objective_scopes.NamedTime(
        scope_s={k: v for k, v in named.scope_s.items() if k != scope},
        scope_calls={k: v for k, v in named.scope_calls.items()
                     if k != scope})
    assert _read(metric, (epoch, lacking)) is None
    with pytest.raises(RuntimeError, match=scope):
        _read(metric, (epoch, lacking), on_chip=True)
