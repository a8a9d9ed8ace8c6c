"""Vectorized sweep engine vs the sequential driver (bit-exactness contract).

(a) one jitted sweep over the Table-2 grid (3 schemes × 3 seeds × 2 step
    sizes) reproduces each config's loss history AND final iterate
    bit-identically to a per-config `run_asysvrg` call;
(b) the `lax.switch` reader dispatch matches the direct `_READERS` functions
    for all three schemes;
plus grouping across heterogeneous M̃ and delay-schedule dispatch checks.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LogisticRegression, SweepSpec, make_grid,
                        plan_sweep, run_asysvrg, run_hogwild, run_sweep)
from repro.core.asysvrg import (
    DELAY_IDS, SCHEME_IDS, _READERS, _delay_schedule_core, by_scheme,
    make_delay_schedule, read_dispatch)
from repro.core.sweep import _group_fn, _row_args
from repro.data.libsvm import make_synthetic_libsvm


@pytest.fixture(scope="module")
def obj():
    ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
    return LogisticRegression(ds.X, ds.y, l2_reg=1e-3)


def _sequential(obj, spec, epochs):
    """The spec's run by its algorithm's own sequential driver."""
    if spec.algo == "hogwild":
        return run_hogwild(obj, epochs, spec.step_size,
                           num_threads=spec.num_threads, decay=spec.decay,
                           scheme=spec.scheme, tau=spec.tau, seed=spec.seed,
                           delay_kind=spec.delay_kind)
    return run_asysvrg(obj, epochs, spec.to_config(), seed=spec.seed,
                       delay_kind=spec.delay_kind)


def _assert_rows_match_sequential(obj, specs, res, epochs, rows=None):
    """Rows ``rows`` (default: all) of ``res`` against ``specs``' own
    sequential runs, bit for bit."""
    for c in (range(len(specs)) if rows is None else rows):
        spec = specs[c]
        seq = _sequential(obj, spec, epochs)
        np.testing.assert_array_equal(
            np.asarray(seq.history, np.float32), res.histories[c],
            err_msg=f"history mismatch for {spec}")
        np.testing.assert_array_equal(
            np.asarray(seq.w, np.float32), res.final_w[c],
            err_msg=f"final iterate mismatch for {spec}")
        assert int(res.total_updates[c]) == seq.total_updates
        np.testing.assert_allclose(res.effective_passes[c],
                                   np.asarray(seq.effective_passes))


def test_sweep_bit_identical_to_sequential_table2_grid(obj):
    """Acceptance: the Table-2 scheme comparison (3 schemes × 3 seeds × 2
    step sizes) from ONE jit matches the per-run Python-loop driver
    bit-for-bit."""
    epochs = 2
    specs = make_grid(schemes=("consistent", "inconsistent", "unlock"),
                      seeds=(0, 1, 2), step_sizes=(0.5, 2.0), taus=(3,),
                      num_threads=4, inner_steps=25)
    res = run_sweep(obj, epochs, specs)
    assert res.histories.shape == (18, epochs + 1)
    _assert_rows_match_sequential(obj, specs, res, epochs)


def test_sweep_groups_heterogeneous_totals(obj):
    """Specs whose M̃ = pM differ compile as separate groups but still land
    bit-identical rows in input order (uniform delays + unlock drop model
    exercised too)."""
    epochs = 2
    specs = [
        SweepSpec(seed=3, scheme="unlock", step_size=1.0, tau=2,
                  num_threads=3, inner_steps=20, delay_kind="uniform"),
        SweepSpec(seed=4, scheme="inconsistent", step_size=0.5, tau=1,
                  num_threads=2, inner_steps=25),
        SweepSpec(seed=5, scheme="consistent", step_size=1.0, tau=0,
                  num_threads=1, inner_steps=40),
    ]
    assert len({3 * 20, 2 * 25, 1 * 40}) == 3   # three distinct M̃ groups
    res = run_sweep(obj, epochs, specs)
    _assert_rows_match_sequential(obj, specs, res, epochs)


def test_read_dispatch_matches_direct_readers():
    """read_dispatch == the _READERS functions, same key, all schemes."""
    tau, dim = 4, 256
    buffer = jnp.tile(jnp.arange(tau + 1, dtype=jnp.float32)[:, None],
                      (1, dim))
    a, m = jnp.asarray(1), jnp.asarray(4)
    key = jax.random.PRNGKey(17)

    def slot_of(age):
        return jnp.mod(age, tau + 1)

    for scheme, reader in _READERS.items():
        want = reader(buffer, slot_of, a, m, key, dim)
        got = read_dispatch(jnp.int32(SCHEME_IDS[scheme]), buffer,
                            jnp.int32(tau), a, m, key, dim)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"scheme {scheme}")


def test_read_dispatch_under_vmap_matches_per_scheme():
    """Per scheme, one read vmapped over rows (keys and ages) == the rows'
    direct calls: a group's rows share its scheme and vary in the rest.
    A vmap that batches the scheme id itself turns `by_scheme`'s switch
    into a select: each row still reads by its own scheme."""
    tau, dim = 3, 64
    buffer = jnp.tile(jnp.arange(tau + 1, dtype=jnp.float32)[:, None],
                      (1, dim))
    a, m = jnp.asarray([0, 1, 2], jnp.int32), jnp.asarray(3)
    keys = jax.random.split(jax.random.PRNGKey(23), 3)
    for scheme, sid in SCHEME_IDS.items():
        batched = jax.vmap(
            lambda a_, k: read_dispatch(sid, buffer, jnp.int32(tau), a_, m,
                                        k, dim))(a, keys)
        for r in range(3):
            direct = read_dispatch(sid, buffer, jnp.int32(tau), a[r], m,
                                   keys[r], dim)
            np.testing.assert_array_equal(np.asarray(batched[r]),
                                          np.asarray(direct),
                                          err_msg=f"scheme {scheme} row {r}")
    ids = jnp.asarray(list(SCHEME_IDS.values()), jnp.int32)
    selected = jax.vmap(lambda sid, a_, k: by_scheme(
        sid, lambda s: read_dispatch(s, buffer, jnp.int32(tau), a_, m, k,
                                     dim)))(ids, a, keys)
    for scheme, sid in SCHEME_IDS.items():
        direct = read_dispatch(sid, buffer, jnp.int32(tau), a[sid], m,
                               keys[sid], dim)
        np.testing.assert_array_equal(np.asarray(selected[sid]),
                                      np.asarray(direct),
                                      err_msg=f"batched scheme {scheme}")


def test_numeric_delay_dispatch_matches_string_api():
    """The numeric-select delay core == the public string API for every kind,
    including the τ=0 collapse to the zero schedule."""
    key = jax.random.PRNGKey(5)
    for tau in (0, 3, 7):
        for kind, did in DELAY_IDS.items():
            want = make_delay_schedule(kind, 50, tau, key)
            eff = DELAY_IDS["zero"] if tau == 0 else did
            got = _delay_schedule_core(jnp.int32(eff), 50, jnp.int32(tau), key)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          err_msg=f"{kind} tau={tau}")


def test_sweep_rejects_bad_specs(obj):
    with pytest.raises(ValueError):
        run_sweep(obj, 1, [])
    with pytest.raises(ValueError):
        run_sweep(obj, 1, [SweepSpec(scheme="nope")])
    with pytest.raises(ValueError):
        run_sweep(obj, 1, [SweepSpec(delay_kind="nope")])
    with pytest.raises(ValueError):
        run_sweep(obj, 1, [SweepSpec(epochs=-1)])
    with pytest.raises(ValueError):
        run_sweep(obj, 0, [SweepSpec()])    # resolved epochs must be >= 1


# ---------------------------------------------------------------------------
# masked per-row epochs
# ---------------------------------------------------------------------------

def test_per_row_epochs_match_independent_shorter_runs(obj):
    """Rows with epochs ∈ {1,2,3} in ONE call: each is bit-identical to an
    independent run of its own length, the frozen tail repeats the final
    loss, and accounting (passes/updates) stops at the row's budget."""
    specs = [SweepSpec(scheme="inconsistent", step_size=0.5, tau=3,
                       num_threads=4, inner_steps=25, seed=7, epochs=e)
             for e in (1, 2, 3)]
    res = run_sweep(obj, 3, specs)
    assert res.histories.shape == (3, 4)
    for c, spec in enumerate(specs):
        seq = run_asysvrg(obj, spec.epochs, spec.to_config(), seed=7)
        np.testing.assert_array_equal(
            np.asarray(seq.history, np.float32),
            res.histories[c, :spec.epochs + 1],
            err_msg=f"history mismatch for epochs={spec.epochs}")
        np.testing.assert_array_equal(np.asarray(seq.w, np.float32),
                                      res.final_w[c])
        assert np.all(res.histories[c, spec.epochs:]
                      == res.histories[c, spec.epochs])
        assert int(res.total_updates[c]) == seq.total_updates
        assert int(res.epochs_per_row[c]) == spec.epochs
        passes, hist = res.curve(c)
        assert len(hist) == spec.epochs + 1
        np.testing.assert_allclose(passes, np.asarray(seq.effective_passes))


def test_epochs_zero_inherits_call_default(obj):
    """epochs=0 rows inherit run_sweep's argument and mix freely with
    explicit budgets; the default row matches a default-length run."""
    specs = [SweepSpec(scheme="consistent", step_size=0.5, tau=3,
                       num_threads=4, inner_steps=25, seed=1),
             SweepSpec(scheme="consistent", step_size=0.5, tau=3,
                       num_threads=4, inner_steps=25, seed=1, epochs=4)]
    res = run_sweep(obj, 2, specs)
    assert list(res.epochs_per_row) == [2, 4]
    seq2 = run_asysvrg(obj, 2, specs[0].to_config(), seed=1)
    seq4 = run_asysvrg(obj, 4, specs[1].to_config(), seed=1)
    np.testing.assert_array_equal(np.asarray(seq2.history, np.float32),
                                  res.histories[0, :3])
    np.testing.assert_array_equal(np.asarray(seq4.history, np.float32),
                                  res.histories[1])
    np.testing.assert_array_equal(np.asarray(seq2.w, np.float32),
                                  res.final_w[0])
    np.testing.assert_array_equal(np.asarray(seq4.w, np.float32),
                                  res.final_w[1])


# ---------------------------------------------------------------------------
# spec normalization + per-row compiled-shape pinning
# ---------------------------------------------------------------------------

def test_svrg_specs_normalized_to_what_executes(obj):
    """svrg rows execute consistent/zero-delay/τ=0; the result's specs (and
    row() records) must say so even when the input spec left the
    asysvrg-flavoured defaults in place."""
    res = run_sweep(obj, 1, [SweepSpec(algo="svrg", step_size=0.5,
                                       num_threads=1, inner_steps=30)])
    s = res.specs[0]
    assert (s.scheme, s.delay_kind, s.tau) == ("consistent", "zero", 0)
    rec = res.row(0)
    assert rec["scheme"] == "consistent" and rec["delay_kind"] == "zero"
    assert rec["epochs"] == 1


def test_svrg_contradictory_tau_raises(obj):
    with pytest.raises(ValueError, match="degenerate"):
        run_sweep(obj, 1, [SweepSpec(algo="svrg", tau=3)])


def test_result_specs_report_derived_tau_and_zero_delay(obj):
    """Convention sentinels are resolved in the result: asysvrg tau=0 means
    τ=p−1, and a genuinely zero-delay row reports delay_kind='zero'."""
    specs = [SweepSpec(scheme="inconsistent", step_size=0.5, tau=0,
                       num_threads=4, inner_steps=25),
             SweepSpec(scheme="consistent", step_size=0.5, tau=0,
                       num_threads=1, inner_steps=25)]
    res = run_sweep(obj, 1, specs)
    assert res.specs[0].tau == 3                       # derived p−1
    assert res.specs[0].delay_kind == "fixed"
    assert res.specs[1].tau == 0                       # p=1 -> genuinely 0
    assert res.specs[1].delay_kind == "zero"


def test_buf_len_pinned_per_row(obj):
    """Adding an unrelated high-τ row must not change another row's group
    key (= compiled program shape): buf_len comes from the row's own
    (τ, threads), not from whichever rows share the group."""
    a = SweepSpec(scheme="inconsistent", step_size=0.5, tau=3,
                  num_threads=4, inner_steps=25)
    b = SweepSpec(scheme="inconsistent", step_size=0.5, tau=50,
                  num_threads=4, inner_steps=25)
    p_alone = plan_sweep(obj, 2, [a])
    p_both = plan_sweep(obj, 2, [a, b])
    key_alone = next(k for k, v in p_alone.groups.items() if 0 in v)
    key_both = next(k for k, v in p_both.groups.items() if 0 in v)
    assert key_alone == key_both
    assert len(p_both.groups) == 2      # the τ=50 row got its own group
    # and the split groups still produce bit-identical rows
    res = run_sweep(obj, 2, [a, b])
    _assert_rows_match_sequential(obj, [a, b], res, 2)


def test_tau_axis_shares_one_group_at_fixed_thread_count(obj):
    """The frontier's τ axis (one thread count, τ ≤ p−1) must stay ONE
    compiled group — buf_len pinning pads to the thread count."""
    specs = [SweepSpec(scheme="inconsistent", step_size=0.5, tau=t,
                       num_threads=4, inner_steps=25) for t in (1, 2, 3)]
    plan = plan_sweep(obj, 2, specs)
    assert len(plan.groups) == 1
    (key,) = plan.groups
    assert key.scheme_id == SCHEME_IDS["inconsistent"]
    assert key.fused is False           # default engine mode is vmap
    assert key.fingerprint == obj.fingerprint()
    assert key.buf_len == 4             # p, not max(τ)+1 of the members


def _computations(hlo: str):
    """{computation name: its lines} of an HLO module's text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return {k: "\n".join(v) for k, v in comps.items()}


@pytest.mark.parametrize("algo", ["asysvrg", "hogwild"])
def test_one_scheme_group_runner_branches_on_its_scheme(obj, algo):
    """A group holds one scheme, which its runner takes unbatched: the
    switch on it around the inner loop compiles to a `conditional` of one
    loop per scheme, so the device runs only the group's reader, and the
    unlock mask only for unlock. A runner that batched the scheme would
    compile it to a select that runs every scheme's loop."""
    specs = [SweepSpec(algo=algo, scheme="inconsistent", step_size=0.5,
                       tau=3, num_threads=4, inner_steps=25, seed=k)
             for k in range(2)]
    plan = plan_sweep(obj, 1, specs)
    (key, members), = plan.groups.items()
    engine = key.engine
    fn, _ = _group_fn(engine, obj=obj, num_data=len(obj.data_args()),
                      epochs=1, total=key.total, buf_len=key.buf_len,
                      option=key.option, drop_prob=0.02)
    args = _row_args(engine, plan.specs, plan.resolved, members,
                     obj.init_flat())
    hlo = jax.jit(fn).lower(*obj.data_args(), *args).compile().as_text()
    comps = _computations(hlo)
    branches = [re.findall(r"%?([\w.\-]+)", b) for b in re.findall(
        r"\bconditional\(.*branch_computations=\{([^}]*)\}", hlo)]
    by_scheme = [b for b in branches if len(b) == len(SCHEME_IDS)]
    assert len(by_scheme) == 1
    assert all(re.search(r"\bwhile\(", comps[c]) for c in by_scheme[0])


def _mixed_grid():
    """Both engines × the three schemes, two seeds each, in one call."""
    return [SweepSpec(algo=algo, scheme=scheme, step_size=0.5, tau=3,
                      num_threads=4, inner_steps=25, seed=seed)
            for algo in ("asysvrg", "hogwild") for scheme in SCHEME_IDS
            for seed in (5, 6)]


@pytest.fixture(scope="module")
def mixed_run(obj):
    specs = _mixed_grid()
    return specs, run_sweep(obj, 2, specs)


@pytest.mark.parametrize("scheme", list(SCHEME_IDS))
@pytest.mark.parametrize("algo", ["asysvrg", "hogwild"])
def test_mixed_scheme_grid_rows_match_their_driver(obj, mixed_run, algo,
                                                   scheme):
    """A grid of both engines and every scheme dispatches one group per
    (engine, scheme); each row equals its own `run_asysvrg` /
    `run_hogwild` bit for bit."""
    specs, res = mixed_run
    plan = plan_sweep(obj, 2, specs)
    assert len(plan.groups) == 6
    rows = [c for c, s in enumerate(specs)
            if s.algo == algo and s.scheme == scheme]
    assert sorted(m for k, m in plan.groups.items()
                  if k.scheme == scheme and k.engine == algo) == [rows]
    _assert_rows_match_sequential(obj, specs, res, 2, rows)
