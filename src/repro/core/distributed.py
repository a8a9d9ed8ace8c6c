"""Distributed AsySVRG for TPU meshes (the paper's insight B, see DESIGN §2).

Three pieces:

1. ``SVRGState`` + ``svrg_direction`` — SVRG as a *gradient estimator* for
   arbitrary param pytrees: v = g(w) − g(w_snap) + g_snap. The train loop
   computes both grads on the same minibatch (the paper's inner loop, with
   minibatches instead of single instances) and any optimizer consumes v.

2. ``snapshot`` steps — the paper's partitioned full-gradient pass: every
   data-parallel worker accumulates grads over its shard of the reference
   batches; the mean is one all-reduce (φ_a semantics, verbatim).

3. ``bounded_staleness_epoch`` — the asynchronous inner loop mapped to SPMD:
   each worker on the `data` axis runs H local SVRG steps on its OWN replica
   (replica divergence carries the paper's coordinate-age mixing, Eq. 10),
   then replicas reconcile by averaging (Option 2) — optionally through a
   compressed collective (core.compression) whose per-worker
   ``ErrorFeedbackState`` is threaded IN AND OUT of the epoch, so the
   compression residual accumulates across epochs (Stich-style EF; a
   residual recreated per epoch would silently discard it). H is the
   staleness bound τ; H=1 is synchronous minibatch SVRG (the τ=0
   degenerate case).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.config import SVRGConfig
from repro.core.compression import ErrorFeedbackState, compressed_update
from repro.utils.tree import tree_add, tree_scale, tree_sub, tree_zeros_like


class SVRGState(NamedTuple):
    """Optimizer-agnostic SVRG snapshot state (lives beside params).

    g_snap doubles as the snapshot-gradient ACCUMULATOR during the epoch
    barrier (Algorithm 1 computes the full gradient with all workers before
    any inner step runs, so no separate buffer is needed — this keeps SVRG
    at exactly 2 extra param-sized states, which is what lets command-r-104b
    + SVRG fit 16 GB/chip)."""
    w_snap: Any        # snapshot parameters u_0
    g_snap: Any        # full gradient ∇f(u_0) (or in-progress accumulator)
    snap_step: jnp.ndarray   # step at which snapshot was taken
    accum_count: jnp.ndarray


def init_svrg_state(params) -> SVRGState:
    return SVRGState(
        w_snap=params,
        g_snap=tree_zeros_like(params),
        snap_step=jnp.zeros((), jnp.int32),
        accum_count=jnp.zeros((), jnp.int32),
    )


def svrg_direction(g, g0, g_snap):
    """v = g − g0 + g_snap (Algorithm 1, Eq. 2), leaf-wise on pytrees."""
    return jax.tree.map(lambda a, b, c: a - b + c, g, g0, g_snap)


def make_svrg_grad_fn(loss_fn: Callable):
    """Returns grad_fn(params, svrg_state, batch) -> (loss, v).

    Two fwd+bwd on the same batch — at w and at w_snap — then the control
    variate. This is the step the multi-pod dry-run lowers for `train_4k`.
    """
    vgrad = jax.value_and_grad(loss_fn)

    def grad_fn(params, svrg_state: SVRGState, batch):
        loss, g = vgrad(params, batch)
        _, g0 = vgrad(svrg_state.w_snap, batch)
        v = svrg_direction(g, g0, svrg_state.g_snap)
        return loss, v

    return grad_fn


# ---------------------------------------------------------------------------
# Snapshot pass (partitioned full gradient)
# ---------------------------------------------------------------------------

def snapshot_begin(svrg_state: SVRGState) -> SVRGState:
    """Start a snapshot pass: zero the accumulator (epoch barrier — no inner
    steps run until finalize, exactly Algorithm 1's structure)."""
    return svrg_state._replace(
        g_snap=tree_zeros_like(svrg_state.g_snap),
        accum_count=jnp.zeros((), jnp.int32),
    )


def snapshot_accumulate(loss_fn: Callable, params, svrg_state: SVRGState,
                        batch) -> SVRGState:
    """One reference-batch contribution to the snapshot gradient.

    Under pjit with the batch sharded over (pod, data), this IS the paper's
    φ_a partitioned pass — each device grads its shard; XLA's reduction over
    the batch dim is the single all-reduce."""
    g = jax.grad(loss_fn)(params, batch)
    return svrg_state._replace(
        g_snap=tree_add(svrg_state.g_snap, g),
        accum_count=svrg_state.accum_count + 1,
    )


def snapshot_finalize(params, svrg_state: SVRGState, step) -> SVRGState:
    """w_snap ← w; g_snap ← mean of accumulated reference grads."""
    cnt = jnp.maximum(svrg_state.accum_count, 1).astype(jnp.float32)
    return SVRGState(
        w_snap=params,
        g_snap=tree_scale(svrg_state.g_snap, 1.0 / cnt),
        snap_step=jnp.asarray(step, jnp.int32),
        accum_count=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Bounded-staleness local SVRG (shard_map over the data axis)
# ---------------------------------------------------------------------------

def init_worker_error_feedback(params, num_workers: int) -> ErrorFeedbackState:
    """Per-worker EF residuals: params-shaped zeros with a leading [W] axis
    (worker w's residual lives at index w, sharded over the `data` axis)."""
    return ErrorFeedbackState(jax.tree.map(
        lambda x: jnp.zeros((num_workers,) + x.shape, x.dtype), params))


def bounded_staleness_epoch(
    mesh: Mesh,
    loss_fn: Callable,                # loss_fn(params, batch) scalar
    params,
    svrg_state: SVRGState,
    local_batches,                    # pytree of arrays [W*H, ...] sharded W over 'data'
    step_size: float,
    cfg: SVRGConfig,
    rng: Optional[jax.Array] = None,
    ef: Optional[ErrorFeedbackState] = None,
):
    """H local SVRG steps per worker, then (optionally compressed) reconcile.

    Each of the W workers on the `data` mesh axis scans H minibatches from
    its own shard, updating a private replica — between reconciles, replica
    coordinates mix updates of different ages exactly as the paper's
    inconsistent/unlock reads do. The closing pmean is Option 2 averaging.

    Returns ``(new_params, new_ef)``. ``ef`` is each worker's PERSISTENT
    error-feedback state ([W]-leading residual tree; None = zeros, i.e. a
    fresh run): the compressor transmits compress(delta + residual) and the
    untransmitted remainder is carried to the NEXT epoch — pass the
    returned state back in. Recreating it every epoch would throw the
    residual away and forfeit the EF convergence guarantee.
    """
    grad_fn = jax.grad(loss_fn)
    w_snap, g_snap = svrg_state.w_snap, svrg_state.g_snap
    method = cfg.compression
    frac = cfg.compression_k
    if rng is None:
        rng = jax.random.PRNGKey(0)
    num_workers = mesh.shape.get("data", 1)
    if ef is None:
        ef = init_worker_error_feedback(params, num_workers)

    def worker(params_rep, w_snap_rep, g_snap_rep, batches, key, residual):
        # shard_map delivers [1, H, local_batch, ...]; drop the worker dim.
        batches = jax.tree.map(lambda x: x[0], batches)
        key = key[0]
        residual = jax.tree.map(lambda x: x[0], residual)

        def body(w, b):
            g = grad_fn(w, b)
            g0 = grad_fn(w_snap_rep, b)
            v = svrg_direction(g, g0, g_snap_rep)
            w = jax.tree.map(lambda wi, vi: wi - step_size * vi, w, v)
            return w, None

        w_local, _ = jax.lax.scan(body, params_rep, batches)
        # reconcile: average replicas (Option 2). With compression, transmit
        # only the compressed delta and re-add to the common base point; the
        # compression error joins this worker's carried residual.
        delta = tree_sub(w_local, params_rep)
        ef_local = ErrorFeedbackState(residual)
        if method != "none":
            delta, ef_local = compressed_update(delta, ef_local, method,
                                                frac, key)
        delta_mean = jax.lax.pmean(delta, "data")
        new_residual = jax.tree.map(lambda x: x[None], ef_local.residual)
        return tree_add(params_rep, delta_mean), new_residual

    keys = jax.random.split(rng, max(2, num_workers))[:num_workers]

    fn = jax.shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(), P(), P(), P("data"), P("data"), P("data")),
        out_specs=(P(), P("data")),
        check_vma=False,
    )
    new_params, new_residual = fn(params, w_snap, g_snap, local_batches,
                                  keys, ef.residual)
    return new_params, ErrorFeedbackState(new_residual)


def reshape_for_workers(batches, num_workers: int, local_steps: int):
    """[W*H, b, ...] -> [W, H, b, ...] worker-major (leaf-wise)."""
    def rs(x):
        assert x.shape[0] == num_workers * local_steps, (
            f"need {num_workers * local_steps} microbatches, got {x.shape[0]}")
        return x.reshape((num_workers, local_steps) + x.shape[1:])
    return jax.tree.map(rs, batches)
