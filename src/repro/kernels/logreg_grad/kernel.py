"""Pallas TPU kernel: fused logistic-regression gradient — the paper's own
inner-loop hot spot, adapted from the CPU original's sparse CSR loop to
dense MXU tiles (DESIGN.md §8).

Two blocked passes over X (the only O(n·p) data):

  pass 1 (margins):  z_b = X[b,:] @ w        — grid (nB, nP), accumulate over
                     p-blocks into z scratch; on the last p-block apply the
                     elementwise σ to produce c_b = −y_b·σ(−y_b z_b)/B.
  pass 2 (gradient): g_p = Σ_b X[b,p]ᵀ c_b   — grid (nP, nB) accumulating
                     over batch blocks in VMEM scratch.

λw is added by ops.py (O(p), not worth a pass). Tiles (128, 512) keep each
operand block ≤ 256 KiB VMEM and feed the MXU 128-lane contractions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_B = 128
BLOCK_P = 512


def _margin_kernel(x_ref, w_ref, y_ref, c_ref, z_scr, *, np_blocks: int,
                   inv_b: float):
    pj = pl.program_id(1)

    @pl.when(pj == 0)
    def _init():
        z_scr[...] = jnp.zeros_like(z_scr)

    x = x_ref[...].astype(jnp.float32)            # [bB, bP]
    w = w_ref[...].astype(jnp.float32)            # [bP, 1]
    z_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(pj == np_blocks - 1)
    def _finish():
        y = y_ref[...].astype(jnp.float32)        # [bB, 1]
        s = jax.nn.sigmoid(-y * z_scr[...])
        c_ref[...] = (-y * s * inv_b).astype(c_ref.dtype)


def margins(X, y, w, interpret: bool = False):
    """X [B, P], y [B, 1], w [P, 1] -> c [B, 1] with c = −y σ(−y Xw)/B."""
    B, P = X.shape
    assert B % BLOCK_B == 0 and P % BLOCK_P == 0, (B, P)
    nB, nP = B // BLOCK_B, P // BLOCK_P
    return pl.pallas_call(
        functools.partial(_margin_kernel, np_blocks=nP, inv_b=1.0 / B),
        grid=(nB, nP),
        in_specs=[
            pl.BlockSpec((BLOCK_B, BLOCK_P), lambda i, j: (i, j)),
            pl.BlockSpec((BLOCK_P, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((BLOCK_B, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_B, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BLOCK_B, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(X, w, y)


def _grad_kernel(x_ref, c_ref, g_ref, acc_scr, *, nb_blocks: int):
    bi = pl.program_id(1)

    @pl.when(bi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)            # [bB, bP]
    c = c_ref[...].astype(jnp.float32)            # [bB, 1]
    acc_scr[...] += jax.lax.dot_general(
        x, c, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(bi == nb_blocks - 1)
    def _finish():
        g_ref[...] = acc_scr[...].astype(g_ref.dtype)


def grad_accum(X, c, interpret: bool = False):
    """X [B, P], c [B, 1] -> g [P, 1] = Xᵀ c (blocked over batch)."""
    B, P = X.shape
    nB, nP = B // BLOCK_B, P // BLOCK_P
    return pl.pallas_call(
        functools.partial(_grad_kernel, nb_blocks=nB),
        grid=(nP, nB),
        in_specs=[
            pl.BlockSpec((BLOCK_B, BLOCK_P), lambda j, i: (i, j)),
            pl.BlockSpec((BLOCK_B, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_P, 1), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((P, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BLOCK_P, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(X, c)
