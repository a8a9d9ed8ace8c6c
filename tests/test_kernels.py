"""Pallas kernel sweeps: shapes x dtypes vs the ref.py oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels.svrg_update import ops as svrg_ops
from repro.kernels.svrg_update.ref import svrg_update_ref
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.logreg_grad import ops as logreg_ops
from repro.kernels.logreg_grad.ref import logreg_grad_ref


# ---------------------------------------------------------------------------
# svrg_update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(64,), (1000,), (129, 7), (8, 64, 33),
                                   (8192,), (1, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_svrg_update_matches_ref(shape, dtype):
    keys = jax.random.split(jax.random.PRNGKey(hash(shape) % 2**31), 4)
    u, g, g0, gf = [jax.random.normal(k, shape).astype(dtype) for k in keys]
    out = svrg_ops.apply_leaf(u, g, g0, gf, 0.07, wd=0.01,
                              interpret=True, force_kernel=True)
    ref = svrg_update_ref(u, g, g0, gf, 0.07, 0.01)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_svrg_update_tree():
    tree = {"a": jnp.ones((33,)), "b": {"c": jnp.full((4, 5), 2.0)}}
    zeros = jax.tree.map(jnp.zeros_like, tree)
    out = svrg_ops.apply_tree(tree, tree, zeros, zeros, 0.5, 0.0,
                              interpret=True, force_kernel=True)
    # v = g - 0 + 0 = tree; u' = u - 0.5 u = 0.5 u
    np.testing.assert_allclose(np.asarray(out["a"]), 0.5 * np.ones(33),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["b"]["c"]), np.ones((4, 5)),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,bq,bk", [(128, 64, 64), (256, 64, 128),
                                     (256, 128, 64)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 32), (False, 0)])
def test_flash_attention_matches_ref(S, bq, bk, causal, window):
    key = jax.random.PRNGKey(S + bq + window)
    ks = jax.random.split(key, 3)
    BH, d = 4, 32
    q, k, v = [jax.random.normal(kk, (BH, S, d)) for kk in ks]
    out = flash_attention(q, k, v, causal=causal, window=window,
                          bq=bq, bk=bk, interpret=True)
    ref = attention_ref(q[None], k[None], v[None],
                        causal=causal, window=window)[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("N,K", [(4, 4), (4, 2), (8, 1)])
def test_gqa_flash_wrapper(N, K):
    key = jax.random.PRNGKey(N * 17 + K)
    B, S, h = 2, 128, 16
    q = jax.random.normal(key, (B, S, N, h))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, K, h))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, K, h))
    out = flash_ops.gqa_flash(q, k, v, causal=True, interpret=True,
                              force_kernel=True, bq=64, bk=64)
    # oracle via jnp path
    ref = flash_ops.gqa_flash(q, k, v, causal=True, force_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (2, 128, 32)).astype(dtype)
    out = flash_attention(q, q, q, causal=True, bq=64, bk=64, interpret=True)
    ref = attention_ref(q[None], q[None], q[None], causal=True)[0]
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# logreg grad (the paper's workload)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,P", [(128, 512), (200, 300), (64, 1024),
                                 (300, 1)])
def test_logreg_grad_matches_ref(B, P):
    key = jax.random.PRNGKey(B + P)
    X = jax.random.normal(key, (B, P))
    y = jnp.sign(jax.random.normal(jax.random.fold_in(key, 1), (B,)) + 0.2)
    w = jax.random.normal(jax.random.fold_in(key, 2), (P,)) * 0.1
    out = logreg_ops.logreg_grad(X, y, w, 1e-4, interpret=True,
                                 force_kernel=True)
    ref = logreg_grad_ref(X, y, w, 1e-4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_logreg_grad_is_true_gradient():
    """Kernel output == autodiff gradient of the objective: validates
    against jax.grad, not just the hand-written ref."""
    key = jax.random.PRNGKey(4)
    B, P = 128, 256
    X = jax.random.normal(key, (B, P))
    y = jnp.sign(jax.random.normal(jax.random.fold_in(key, 1), (B,)) + 0.2)
    w = jax.random.normal(jax.random.fold_in(key, 2), (P,)) * 0.1

    def loss(w):
        return jnp.mean(jnp.logaddexp(0.0, -y * (X @ w))) \
            + 0.5e-4 * 2 * 0.5 * jnp.sum(w * w)

    g_auto = jax.grad(loss)(w)
    g_kern = logreg_ops.logreg_grad(X, y, w, 1e-4, interpret=True,
                                    force_kernel=True)
    np.testing.assert_allclose(np.asarray(g_kern), np.asarray(g_auto),
                               atol=1e-5)


# ------------------------------------------------- unified kernel dispatch
def test_kernel_mode_env_override_wins(monkeypatch):
    """$REPRO_KERNEL_MODE beats flags and backend sniff for ALL kernels;
    bad values raise."""
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, "interpret")
    assert dispatch.kernel_mode() == "interpret"
    assert dispatch.kernel_mode(force_kernel=True) == "interpret"
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, "reference")
    assert dispatch.kernel_mode(interpret=True, force_kernel=True) \
        == "reference"
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, "warp")
    with pytest.raises(ValueError, match="REPRO_KERNEL_MODE"):
        dispatch.kernel_mode()


def test_kernel_mode_historical_contract(monkeypatch):
    """Without the env var the unified helper reproduces the historical
    per-kernel behaviour: kernel body iff force_kernel or TPU backend,
    interpreter iff asked."""
    monkeypatch.delenv(dispatch.KERNEL_MODE_ENV, raising=False)
    monkeypatch.setattr(dispatch, "kernel_backend", lambda: "cpu")
    assert dispatch.kernel_mode() == "reference"
    assert dispatch.kernel_mode(interpret=True) == "reference"
    assert dispatch.kernel_mode(interpret=True, force_kernel=True) \
        == "interpret"
    assert dispatch.kernel_mode(force_kernel=True) == "compiled"
    monkeypatch.setattr(dispatch, "kernel_backend", lambda: "tpu")
    assert dispatch.kernel_mode() == "compiled"
    assert dispatch.kernel_mode(interpret=True) == "interpret"
