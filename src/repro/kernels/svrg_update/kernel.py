"""Pallas TPU kernel: fused SVRG control-variate parameter update.

Why a kernel: the inner-loop update reads FOUR param-sized arrays
(u, g, g0, gf) and writes one — pure HBM traffic, zero reuse. Unfused, XLA
may materialize v = g − g0 + gf as an intermediate (6 streams); the fused
kernel is exactly 4 reads + 1 write at peak HBM bandwidth. Tiles are
(8·ROWS, 128)-aligned for the VPU lanes; lr is scalar-prefetched via a
(1,1) SMEM-like operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
BLOCK_ROWS = 64          # rows of 128 lanes per VMEM tile (64*128*4B = 32 KiB/operand)


def _update_kernel(lr_ref, u_ref, g_ref, g0_ref, gf_ref, out_ref, *, wd: float):
    lr = lr_ref[0, 0]
    u = u_ref[...]
    v = g_ref[...] - g0_ref[...] + gf_ref[...]
    if wd:
        v = v + wd * u.astype(v.dtype)
    out_ref[...] = (u.astype(jnp.float32) - lr * v.astype(jnp.float32)).astype(out_ref.dtype)


def svrg_update_2d(u, g, g0, gf, lr, wd: float = 0.0,
                   interpret: bool = False):
    """u, g, g0, gf: [R, 128] with R % BLOCK_ROWS == 0. lr: [1,1] f32."""
    R = u.shape[0]
    assert u.shape[1] == LANES and R % BLOCK_ROWS == 0, u.shape
    grid = (R // BLOCK_ROWS,)
    block = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_update_kernel, wd=wd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),   # lr (broadcast scalar)
            block, block, block, block,
        ],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        interpret=interpret,
        name="svrg_update",
    )(lr, u, g, g0, gf)
