"""Objectives: the pluggable protocol the whole sweep stack optimizes, and
the paper's own instance (L2-regularized logistic regression, paper §5):

    f(w) = (1/n) Σ_i log(1 + exp(-y_i x_i·w)) + (λ/2)||w||²

The engine (`repro.core.asysvrg` / `repro.core.hogwild` / `repro.core.sweep`
and the service/server tiers above them) is objective-agnostic: anything
implementing :class:`Objective` — pytree params ``w``, per-sample gradients,
a fixed-order loss — runs through the same compiled sweep groups, the same
runner cache, and the same HTTP tier. `repro.core.objectives` adds an MLP
LM and a nonconvex-regularized logistic objective on top of this protocol.

## The vmap-bitwise-stable contract

The sweep engine runs a batch of configurations through `jax.vmap` and must
reproduce the sequential driver BIT-identically — and a row's bits must not
depend on which other rows share the batch (that is what makes request
coalescing, stable-width padding and row sharding bit-exact). XLA:CPU keeps
row-reduces over a trailing axis and elementwise ops bitwise-stable under
an added leading batch axis, but changes the summation order of full
reductions to a scalar (jnp.mean, jnp.vdot, X @ w). Every `Objective`
implementation must therefore build its ``*_stable`` methods from:

  * elementwise ops and broadcasts;
  * single-axis reduces over a TRAILING axis (row-reduces, logsumexp,
    keepdims-mean) — express a matmul ``x @ W`` as
    ``sum(x[..., None, :] * W.T, axis=-1)`` when its bits matter;
  * `_fixed_order_sum` (a lax.scan) for any accumulation to a scalar or
    across samples.

`jax.grad` of a function built from these pieces stays stable (pinned by
tests/test_objective_protocol.py). The contract is CALIBRATED ON XLA:CPU;
re-validate per backend.
"""
from __future__ import annotations

import zlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.tree import tree_ravel, tree_unravel_fn


def _log1pexp(z):
    """Numerically stable log(1 + e^z)."""
    return jnp.logaddexp(0.0, z)


# ---------------------------------------------------------------------------
# vmap-bitwise-stable formulations (used by the AsySVRG engine + sweep)
#
# The sweep engine (repro.core.sweep) runs a batch of configurations through
# jax.vmap and must reproduce the sequential driver BIT-identically. XLA:CPU
# keeps row-reduces over a trailing axis and elementwise ops bitwise-stable
# under an added leading batch axis, but changes the summation order of
# full reductions to a scalar (jnp.mean, jnp.vdot, X @ w). The functions
# below therefore use only row-reduces plus a fixed-order lax.scan for
# scalar accumulation.
# ---------------------------------------------------------------------------

def _fixed_order_sum(v):
    """Σ v_i accumulated strictly in index order (vmap-bitwise-stable)."""
    acc, _ = jax.lax.scan(lambda a, x: (a + x, None),
                          jnp.zeros((), v.dtype), v)
    return acc


def _margins_stable(X, y, w):
    """y ⊙ (X w) as a row-reduce (stable under a leading batch axis on w)."""
    return y * jnp.sum(X * w[None, :], axis=1)


def loss_fixed_order(X, y, l2: float, w):
    """f(w) with fixed-order reductions; equals LogisticRegression.loss up to
    summation order (differences are O(n·eps))."""
    t = _log1pexp(-_margins_stable(X, y, w))
    n = X.shape[0]
    return _fixed_order_sum(t) / n + 0.5 * l2 * _fixed_order_sum(w * w)


def full_grad_stable(X, y, l2: float, w):
    """∇f(w) via row-reduces only (vmap-bitwise-stable)."""
    n = X.shape[0]
    s = jax.nn.sigmoid(-_margins_stable(X, y, w))
    return jnp.sum((-(y * s))[:, None] * X, axis=0) / n + l2 * w


def sample_grad_stable(X, y, l2: float, w, i):
    """∇f_i(w) (vmap-bitwise-stable)."""
    x = X[i]
    yi = y[i]
    s = jax.nn.sigmoid(-yi * jnp.sum(x * w, axis=-1))
    return -yi * s * x + l2 * w


# ---------------------------------------------------------------------------
# The pluggable objective protocol
# ---------------------------------------------------------------------------

class Objective:
    """Base class for pluggable objectives: pytree params, per-sample grads.

    A subclass provides the PURE pieces (they receive ``data`` — the tuple
    `data_args` returns — as an argument and must not read arrays off
    ``self``; only static config may live in the closure, so that two
    instances with equal `runner_static_key` trace identical programs and
    share one cached runner across tenants):

      * ``n`` — number of samples (set in ``__init__``);
      * :meth:`data_args` — tuple of jnp arrays/scalars entering the
        compiled runner as RUNTIME arguments (replicated under shard_map);
      * :meth:`init_params` — the w₀ pytree (single array, or a possibly
        nested dict of same-dtype arrays);
      * :meth:`loss_fixed_order(data, w)` — f(w), fixed-order reductions;
      * :meth:`full_grad_stable(data, w)` — ∇f(w) as a pytree;
      * :meth:`sample_grad_stable(data, i, w)` — ∇f_i(w) as a pytree;
      * :meth:`static_key` — hashable tuple of everything (beyond data
        shapes) that changes the traced program.

    All three math methods must obey the vmap-bitwise-stable contract in
    the module docstring.

    The base supplies the flat-vector adapters the engine actually calls
    (`flat_loss` / `flat_full_grad` / `flat_sample_grad` — ravel/unravel
    is bit-exact data movement, see `repro.utils.tree`), fingerprinting
    for cache/checkpoint keys, and serializable `param_shapes` metadata
    the wire format round-trips.
    """

    n: int

    # -- subclass-provided pieces -------------------------------------------
    def data_args(self) -> Tuple:
        raise NotImplementedError

    def init_params(self):
        raise NotImplementedError

    def loss_fixed_order(self, data, w):                  # noqa: ARG002
        raise NotImplementedError

    def full_grad_stable(self, data, w):                  # noqa: ARG002
        raise NotImplementedError

    def sample_grad_stable(self, data, i, w):             # noqa: ARG002
        raise NotImplementedError

    def static_key(self) -> Tuple:
        return ()

    # -- sizing / template (cached: shapes are static per instance) ---------
    @property
    def _template(self):
        tpl = getattr(self, "_template_cache", None)
        if tpl is None:
            tpl = self.init_params()
            self._template_cache = tpl
        return tpl

    @property
    def flat_dim(self) -> int:
        """Total parameter count — the engine's per-row vector width."""
        return int(sum(int(np.prod(x.shape)) if x.shape else 1
                       for x in jax.tree.leaves(self._template)))

    def num_samples(self, data) -> int:
        """n, derived from the runtime data (trace-time constant). The
        default assumes the first data arg is sample-leading."""
        return data[0].shape[0]

    # -- flat <-> pytree bridge ---------------------------------------------
    def ravel_params(self, tree):
        return tree_ravel(tree)

    def unravel_params(self, flat):
        fn = getattr(self, "_unravel_cache", None)
        if fn is None:
            fn = tree_unravel_fn(self._template)
            self._unravel_cache = fn
        return fn(flat)

    def as_flat(self, w):
        """Accept params as a pytree OR an already-flat vector."""
        if (hasattr(w, "ndim") and getattr(w, "ndim", None) == 1
                and not isinstance(w, (dict, list, tuple))):
            w = jnp.asarray(w)
            if w.shape[0] != self.flat_dim:
                raise ValueError(
                    f"flat params have {w.shape[0]} entries, objective "
                    f"expects {self.flat_dim}")
            return w
        return self.ravel_params(w)

    def init_flat(self):
        return self.ravel_params(self.init_params())

    # -- engine-facing flat adapters ----------------------------------------
    # Subclasses whose params ARE a flat vector (logreg and friends) should
    # override these to call their math directly — zero indirection, and
    # the compiled graph is unchanged from the pre-protocol engine.
    def flat_loss(self, data, w_flat):
        return self.loss_fixed_order(data, self.unravel_params(w_flat))

    def flat_full_grad(self, data, w_flat):
        return self.ravel_params(
            self.full_grad_stable(data, self.unravel_params(w_flat)))

    def flat_sample_grad(self, data, i, w_flat):
        return self.ravel_params(
            self.sample_grad_stable(data, i, self.unravel_params(w_flat)))

    # -- serial-driver conveniences (pytree in, pytree out) ------------------
    # Defaults delegate to the stable math; subclasses may override with
    # faster (non-vmap-stable) formulations for standalone use.
    def loss(self, w):
        return self.loss_fixed_order(self.data_args(), w)

    def full_grad(self, w):
        return self.full_grad_stable(self.data_args(), w)

    def sample_grad(self, w, i):
        return self.sample_grad_stable(self.data_args(), i, w)

    # -- identity ------------------------------------------------------------
    def runner_static_key(self) -> Tuple:
        """Hashable program identity (joined with data shapes/dtypes in the
        runner-cache key): instances agreeing here MUST trace identical
        group programs."""
        return (type(self).__name__,) + tuple(self.static_key())

    def fingerprint(self) -> int:
        """Digest of the objective's identity AND its numeric data (pytree-
        general: every `data_args` leaf's bytes). Joins the sweep group key
        — rows of different objectives never share a compiled group — and
        pins checkpoint-resume jobs to their exact dataset. Memoized: the
        data is immutable for the objective's lifetime."""
        fp = getattr(self, "_fingerprint_cache", None)
        if fp is None:
            fp = zlib.crc32(repr(self.runner_static_key()).encode())
            for leaf in jax.tree.leaves(self.data_args()):
                arr = np.ascontiguousarray(np.asarray(leaf))
                fp = zlib.crc32(arr.tobytes(),
                                zlib.crc32(str(arr.dtype).encode(), fp))
            self._fingerprint_cache = fp
        return fp

    def param_shapes(self) -> Tuple:
        """Serializable ((path, shape, dtype), ...) description of the param
        pytree — `SweepResult` carries it so a wire round-trip can rebuild
        pytree params bit-exactly. A single bare array is ``(("", shape,
        dtype),)``; dict trees use "/"-joined key paths."""
        out = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self._template)[0]:
            keys = []
            for entry in path:
                key = getattr(entry, "key", getattr(entry, "idx", None))
                keys.append(str(key))
            out.append(("/".join(keys), tuple(leaf.shape), str(leaf.dtype)))
        return tuple(out)


def params_from_flat(flat: np.ndarray, param_shapes):
    """Rebuild a param pytree from a flat vector + `Objective.param_shapes`
    metadata (numpy-side; the wire-format consumer). A single unnamed leaf
    comes back as the bare (reshaped) array; named leaves as a nested dict."""
    if not param_shapes:
        return flat
    arrays = []
    off = 0
    for _, shape, dtype in param_shapes:
        size = int(np.prod(shape)) if shape else 1
        arrays.append(np.asarray(flat[off:off + size], dtype)
                      .reshape(tuple(shape)))
        off += size
    if off != len(flat):
        raise ValueError(f"param_shapes cover {off} entries, flat vector "
                         f"has {len(flat)}")
    if len(param_shapes) == 1 and param_shapes[0][0] == "":
        return arrays[0]
    tree: Dict = {}
    for (path, _, _), arr in zip(param_shapes, arrays):
        node = tree
        keys = path.split("/")
        for key in keys[:-1]:
            node = node.setdefault(key, {})
        node[keys[-1]] = arr
    return tree


# ---------------------------------------------------------------------------
# Named-objective registry (the service/server tier's wire addressing):
# `SweepSpec.objective` names a registered instance; empty string means
# "the call's default objective".
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, "Objective"] = {}


def register_objective(name: str, obj: "Objective") -> "Objective":
    """Register an objective instance under ``name`` (re-registering a name
    replaces it — tests and notebook reloads rebuild objectives freely)."""
    if not name:
        raise ValueError("objective name must be non-empty")
    if not isinstance(obj, Objective):
        raise TypeError(f"expected an Objective, got {type(obj).__name__}")
    _REGISTRY[name] = obj
    return obj


def get_objective(name: str) -> "Objective":
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no objective registered under {name!r} "
            f"(registered: {sorted(_REGISTRY)})") from None


def registered_objectives() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def unregister_objective(name: str) -> None:
    _REGISTRY.pop(name, None)


class LogisticRegression(Objective):
    """Stateless objective bound to a dataset (X, y, λ) — the paper's own
    workload, now one `Objective` among several. Params are a single flat
    (p,) vector, so the flat adapters below bypass the generic
    ravel/unravel entirely: the engine's compiled graphs are IDENTICAL to
    the pre-protocol ones (regression-pinned in
    tests/test_objective_protocol.py)."""

    def __init__(self, X, y, l2_reg: float = 1e-4):
        self.X = jnp.asarray(X)
        self.y = jnp.asarray(y)
        self.l2 = float(l2_reg)
        self.n, self.p = self.X.shape

    # -- protocol ------------------------------------------------------------
    def data_args(self) -> Tuple:
        return (self.X, self.y, jnp.float32(self.l2))

    def init_params(self):
        return jnp.zeros(self.p)

    def static_key(self) -> Tuple:
        return ()

    def loss_fixed_order(self, data, w):
        X, y, l2 = data
        return loss_fixed_order(X, y, l2, w)

    def full_grad_stable(self, data, w):
        X, y, l2 = data
        return full_grad_stable(X, y, l2, w)

    def sample_grad_stable(self, data, i, w):
        X, y, l2 = data
        return sample_grad_stable(X, y, l2, w, i)

    # flat == pytree for a (p,) parameter vector: skip the generic bridge
    flat_loss = loss_fixed_order
    flat_full_grad = full_grad_stable

    def flat_sample_grad(self, data, i, w_flat):
        X, y, l2 = data
        return sample_grad_stable(X, y, l2, w_flat, i)

    # -- objective ---------------------------------------------------------
    def loss(self, w) -> jnp.ndarray:
        margins = self.y * (self.X @ w)
        return jnp.mean(_log1pexp(-margins)) + 0.5 * self.l2 * jnp.vdot(w, w)

    # -- gradients ---------------------------------------------------------
    def full_grad(self, w) -> jnp.ndarray:
        """∇f(w) — the snapshot full gradient of Algorithm 1."""
        margins = self.y * (self.X @ w)
        s = jax.nn.sigmoid(-margins)             # σ(-y x·w)
        return (-(self.y * s) @ self.X) / self.n + self.l2 * w

    def partial_full_grad(self, w, lo: int, size: int) -> jnp.ndarray:
        """Partitioned full-gradient contribution (one thread's φ_a).

        Returns an UN-normalized sum over rows [lo, lo+size); the caller sums
        the partitions and divides by n — exactly the paper's parallel
        snapshot pass.
        """
        Xs = jax.lax.dynamic_slice_in_dim(self.X, lo, size, 0)
        ys = jax.lax.dynamic_slice_in_dim(self.y, lo, size, 0)
        margins = ys * (Xs @ w)
        s = jax.nn.sigmoid(-margins)
        return -(ys * s) @ Xs

    def sample_grad(self, w, i) -> jnp.ndarray:
        """∇f_i(w) for one instance (the paper's inner-loop gradient)."""
        x = self.X[i]
        yi = self.y[i]
        s = jax.nn.sigmoid(-yi * jnp.dot(x, w))
        return -yi * s * x + self.l2 * w

    def minibatch_grad(self, w, idx) -> jnp.ndarray:
        """Mean gradient over a batch of indices (beyond-paper batching)."""
        Xb = self.X[idx]
        yb = self.y[idx]
        s = jax.nn.sigmoid(-yb * (Xb @ w))
        return (-(yb * s) @ Xb) / idx.shape[0] + self.l2 * w

    # -- constants for the theory-facing tests ------------------------------
    def smoothness(self) -> float:
        row_sq = jnp.sum(self.X * self.X, axis=1)
        return float(jnp.max(row_sq) / 4.0 + self.l2)

    def strong_convexity(self) -> float:
        return self.l2

    def optimum(self, tol: float = 1e-12, max_iter: int = 5000) -> Tuple[jnp.ndarray, float]:
        """High-accuracy reference optimum via deterministic gradient descent
        with backtracking-free fixed step 1/L (used to compute the paper's
        "gap < 1e-4" stopping metric)."""
        L = self.smoothness()
        step = 1.0 / L

        def body(carry, _):
            w, = carry
            g = self.full_grad(w)
            return (w - step * g,), None

        (w,), _ = jax.lax.scan(body, (jnp.zeros(self.p),), None, length=max_iter)
        return w, float(self.loss(w))


# Names of the ELL objective's device work, opened inside the epoch cores'
# scopes (repro.core.asysvrg.SCOPES): `ell_grad` inside `sample_grad`,
# `ell_snapshot` inside `snapshot`.
ELL_GRAD_SCOPE = "ell_grad"          # one per-sample gradient: gather, scatter
ELL_SNAPSHOT_SCOPE = "ell_snapshot"  # the scatter-add full gradient
ELL_SCOPES = (ELL_GRAD_SCOPE, ELL_SNAPSHOT_SCOPE)
_SQUARES_BLOCK = 1024


def _ell_margins_stable(cols, vals, y, w):
    """y ⊙ (X w) over ELL rows: each row's nonzeros gathered from ``w`` and
    reduced over the trailing axis."""
    return y * jnp.sum(vals * w[cols], axis=-1)


def _blocked_sum_stable(v):
    """Σ v_i for a long vector: trailing-axis sums over blocks of
    `_SQUARES_BLOCK`, then a fixed-order sum of the blocks. The zeros that
    pad the last block add nothing. A scan over a million coordinates
    would cost a loop step each on the device."""
    blocks = jnp.pad(v, (0, -v.shape[0] % _SQUARES_BLOCK))
    return _fixed_order_sum(jnp.sum(blocks.reshape(-1, _SQUARES_BLOCK),
                                    axis=-1))


class EllLogisticRegression(Objective):
    """`LogisticRegression`'s f(w) over sparse rows in ELL layout: for each
    sample its column ids ``cols`` (n, nnz) int32 and values ``vals``
    (n, nnz), padded to a fixed number of nonzeros per row (a padding slot
    holds value 0 at any column). X is never densified, so a dataset such
    as news20 (p = 1,355,191, 108 GB dense) fits on one chip.

    No data shape carries p, so it is static config (`static_key`): two
    instances that differ only in p never share a compiled runner. Params
    are a flat (p,) vector, so the flat adapters are the math itself.

    The math keeps the vmap-bitwise-stable contract: margins gather each
    row's coordinates and reduce over the trailing axis; the per-sample
    gradient scatters the scaled row into ``l2 * w`` (a row's columns are
    distinct, so no two updates meet); the snapshot gradient scatter-adds
    one row at a time in a fixed-order scan, so its summation order does
    not depend on the batch of rows it runs in.
    """

    def __init__(self, cols, vals, y, p: int, l2_reg: float = 1e-4):
        self.cols = jnp.asarray(cols, jnp.int32)
        self.vals = jnp.asarray(vals)
        self.y = jnp.asarray(y)
        self.p = int(p)
        self.l2 = float(l2_reg)
        self.n = self.cols.shape[0]

    # -- protocol ------------------------------------------------------------
    def data_args(self) -> Tuple:
        return (self.cols, self.vals, self.y, jnp.float32(self.l2))

    def init_params(self):
        return jnp.zeros(self.p)

    def static_key(self) -> Tuple:
        return (self.p,)

    def loss_fixed_order(self, data, w):
        cols, vals, y, l2 = data
        t = _log1pexp(-_ell_margins_stable(cols, vals, y, w))
        return (_fixed_order_sum(t) / y.shape[0]
                + 0.5 * l2 * _blocked_sum_stable(w * w))

    def full_grad_stable(self, data, w):
        cols, vals, y, l2 = data
        with jax.named_scope(ELL_SNAPSHOT_SCOPE):
            s = jax.nn.sigmoid(-_ell_margins_stable(cols, vals, y, w))

            def add_row(g, row):
                c, v, r = row
                return g.at[c].add(r * v), None

            g, _ = jax.lax.scan(add_row, jnp.zeros_like(w),
                                (cols, vals, -(y * s)))
            return g / y.shape[0] + l2 * w

    def sample_grad_stable(self, data, i, w):
        cols, vals, y, l2 = data
        # l2·w is p-wide and takes in the read of w; outside the scope, so
        # that the fusion computing it is not named after the row's work
        g = l2 * w
        with jax.named_scope(ELL_GRAD_SCOPE):
            c, v, yi = cols[i], vals[i], y[i]
            s = jax.nn.sigmoid(-yi * jnp.sum(v * w[c], axis=-1))
            return g.at[c].add(-yi * s * v)

    # flat == pytree for a (p,) parameter vector: skip the generic bridge
    flat_loss = loss_fixed_order
    flat_full_grad = full_grad_stable
    flat_sample_grad = sample_grad_stable
