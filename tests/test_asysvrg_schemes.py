"""Reading-scheme semantics + delay-schedule invariants (paper §4.1–4.2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional dev dep (requirements-dev.txt); only the property test needs it
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.config import SVRGConfig
from repro.core import LogisticRegression, make_delay_schedule, run_asysvrg
from repro.core.asysvrg import (
    SCHEME_IDS, _read_consistent, _read_inconsistent, _read_unlock,
    read_dispatch)
from repro.data.libsvm import make_synthetic_libsvm


@pytest.fixture(scope="module")
def obj():
    ds = make_synthetic_libsvm("rcv1", seed=2, scale=0.02)
    return LogisticRegression(ds.X, ds.y, l2_reg=1e-3)


def _check_delay_bounds(num, tau, seed):
    """0 ≤ d_m ≤ min(m, τ) — the paper's bounded-delay requirement."""
    for kind in ("fixed", "uniform", "zero"):
        d = np.asarray(make_delay_schedule(
            kind, num, tau, jax.random.PRNGKey(seed)))
        m = np.arange(num)
        assert (d >= 0).all()
        assert (d <= np.minimum(m, tau)).all()


@pytest.mark.parametrize("num,tau,seed", [(1, 0, 0), (17, 3, 1), (256, 32, 2),
                                          (2000, 8, 3)])
def test_delay_schedule_bounded(num, tau, seed):
    _check_delay_bounds(num, tau, seed)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 2000), st.integers(0, 32), st.integers(0, 10))
    def test_delay_schedule_bounded_property(num, tau, seed):
        _check_delay_bounds(num, tau, seed)
else:
    @pytest.mark.skip(reason="hypothesis not installed (requirements-dev.txt)")
    def test_delay_schedule_bounded_property():
        pass


def _mk_buffer(tau, dim, key):
    # buffer[j] = iterate of age j (distinct constant per age for testing)
    return jnp.tile(jnp.arange(tau + 1, dtype=jnp.float32)[:, None],
                    (1, dim))


def test_consistent_read_is_single_age():
    """Consistent reading returns ONE whole iterate (locked read)."""
    tau, dim = 4, 16
    buf = _mk_buffer(tau, dim, None)
    got = _read_consistent(buf, lambda a: jnp.mod(a, tau + 1),
                           jnp.asarray(2), jnp.asarray(4),
                           jax.random.PRNGKey(0), dim)
    assert len(np.unique(np.asarray(got))) == 1     # all coords same age


def test_inconsistent_read_mixes_two_adjacent_ages():
    """Eq. 10: û mixes coordinates of EXACTLY ages a and a+1."""
    tau, dim = 4, 512
    buf = _mk_buffer(tau, dim, None)
    got = np.asarray(_read_inconsistent(
        buf, lambda a: jnp.mod(a, tau + 1), jnp.asarray(1), jnp.asarray(4),
        jax.random.PRNGKey(1), dim))
    ages = np.unique(got)
    assert set(ages).issubset({1.0, 2.0})
    assert len(ages) == 2    # with 512 coords both ages appear whp


def test_unlock_read_spans_full_window():
    """Unlock: coordinate ages span the whole [a, m] window."""
    tau, dim = 4, 2048
    buf = _mk_buffer(tau, dim, None)
    got = np.asarray(_read_unlock(
        buf, lambda a: jnp.mod(a, tau + 1), jnp.asarray(0), jnp.asarray(4),
        jax.random.PRNGKey(2), dim))
    ages = set(np.unique(got))
    assert ages == {0.0, 1.0, 2.0, 3.0, 4.0}


def _gather_unlock(buffer, tau, a, m, key, dim):
    """The unlock read as a per-coordinate gather: the same ages from the
    same key, then ``buffer[slots, arange(dim)]``."""
    span = (m - a + 1).astype(jnp.float32)
    ages = a + jnp.floor(jax.random.uniform(key, (dim,)) * span).astype(jnp.int32)
    return buffer[jnp.mod(ages, tau + 1), jnp.arange(dim)]


def _reference_read(scheme, buffer, tau, a, m, key, dim):
    """What a scheme reads: the locked readers themselves, the unlock read
    as the gather."""
    if scheme == "unlock":
        return _gather_unlock(buffer, tau, a, m, key, dim)
    reader = (_read_consistent if scheme == "consistent"
              else _read_inconsistent)
    return reader(buffer, lambda age: jnp.mod(age, tau + 1), a, m, key, dim)


def _signed_buffer(buf_len, dim, seed):
    """Distinct values in every slot, with −0.0 and +0.0 in whole columns:
    a read that took a wrong slot, or summed where it should select,
    changes bits."""
    buf = np.random.default_rng(seed).standard_normal(
        (buf_len, dim)).astype(np.float32)
    buf[:, 0::7] = -0.0
    buf[:, 3::7] = 0.0
    return jnp.asarray(buf)


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("tau,buf_len,signed_zeros", [
    (0, 1, False), (4, 5, False), (9, 10, False),
    (4, 16, False),                      # padded beyond τ + 1
    (9, 10, True),                       # −0.0 and +0.0 entries
    (299, 300, True),                    # a ring of 300 slots
], ids=["tau0", "tau4", "tau9", "tau4_padded16", "negative_zero",
        "tau299_long_ring"])
def test_unlock_read_equals_gather_bitwise(tau, buf_len, signed_zeros):
    """The unlock read returns, bit for bit, what a per-coordinate gather
    from the ring buffer returns with the same key."""
    dim, m = 1000, 2 * buf_len + 3           # the ages wrap round the ring
    buf = (_signed_buffer(buf_len, dim, tau) if signed_zeros else
           jnp.asarray(np.random.default_rng(tau).standard_normal(
               (buf_len, dim)), jnp.float32))
    tau_, m_ = jnp.asarray(tau), jnp.asarray(m)
    a = jnp.maximum(m_ - tau_, 0)
    key = jax.random.PRNGKey(7)
    got = _read_unlock(buf, lambda age: jnp.mod(age, tau_ + 1), a, m_, key,
                       dim)
    want = _gather_unlock(buf, tau_, a, m_, key, dim)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    if signed_zeros:
        assert np.signbit(np.asarray(got)[0::7]).all()
        assert not np.signbit(np.asarray(got)[3::7]).any()


def test_unlock_read_equals_gather_bitwise_vmap_mixed_schemes():
    """Under `vmap` over rows of mixed schemes and τ (as the sweep runs
    them), `read_dispatch` gives each unlock row the gather's bits and the
    locked rows their own readers' bits."""
    dim, buf_len = 1000, 10
    schemes = ["consistent", "inconsistent", "unlock", "unlock",
               "inconsistent", "unlock"]
    taus = jnp.asarray([9, 4, 9, 0, 9, 4])
    ms = jnp.asarray([31, 12, 17, 5, 3, 40])
    ids = jnp.asarray([SCHEME_IDS[s] for s in schemes])
    a = jnp.maximum(ms - taus, 0)
    keys = jax.random.split(jax.random.PRNGKey(11), len(schemes))
    bufs = jnp.stack([_signed_buffer(buf_len, dim, r)
                      for r in range(len(schemes))])

    got = jax.jit(jax.vmap(
        lambda s, b, t, a_, m, k: read_dispatch(s, b, t, a_, m, k, dim)))(
            ids, bufs, taus, a, ms, keys)
    for r, scheme in enumerate(schemes):
        want = _reference_read(scheme, bufs[r], taus[r], a[r], ms[r], keys[r],
                               dim)
        np.testing.assert_array_equal(_bits(got[r]), _bits(want),
                                      err_msg=f"row {r} ({scheme})")


@pytest.mark.parametrize("scheme", list(SCHEME_IDS))
def test_single_slot_read_is_the_one_iterate(scheme):
    """At τ = 0 the buffer holds one iterate: `read_dispatch` returns it,
    bit for bit what the scheme's own reader returns."""
    dim, m = 1000, 6
    buf = _signed_buffer(1, dim, 3)
    tau, m_ = jnp.asarray(0), jnp.asarray(m)
    key = jax.random.PRNGKey(5)
    got = read_dispatch(jnp.asarray(SCHEME_IDS[scheme]), buf, tau, m_, m_,
                        key, dim)
    want = _reference_read(scheme, buf, tau, m_, m_, key, dim)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    np.testing.assert_array_equal(_bits(got), _bits(buf[0]))


@pytest.mark.parametrize("delay_kind", ["fixed", "uniform"])
def test_convergence_robust_to_delay_schedule(obj, delay_kind):
    cfg = SVRGConfig(scheme="inconsistent", step_size=2.0, num_threads=8,
                     tau=7)
    res = run_asysvrg(obj, epochs=4, cfg=cfg, seed=5, delay_kind=delay_kind)
    assert res.history[-1] < res.history[0]
    assert all(b <= a * 1.05 for a, b in zip(res.history, res.history[1:]))


def test_larger_tau_never_diverges_smaller_rate(obj):
    """More staleness (larger τ) can slow but must not break convergence
    at a conservative step size (Theorem 1's qualitative content)."""
    gaps = {}
    for tau in (0, 4, 16):
        cfg = SVRGConfig(scheme="consistent", step_size=0.5,
                         num_threads=tau + 1, tau=tau)
        res = run_asysvrg(obj, epochs=3, cfg=cfg, seed=6)
        gaps[tau] = res.history[-1]
    assert gaps[16] < res.history[0]            # still converging
    assert gaps[0] <= gaps[16] * 1.1            # τ=0 at least as good
