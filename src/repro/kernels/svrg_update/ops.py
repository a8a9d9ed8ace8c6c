"""Public wrapper for the fused SVRG update: pytree + padding handling.

`apply_tree` flattens every leaf to (rows, 128) tiles (zero-padded), runs the
kernel per leaf, and restores shapes. Mode selection (compiled / interpret /
jnp reference) goes through `repro.kernels.dispatch.kernel_mode` — the one
policy all kernels share.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import kernel_mode
from repro.kernels.svrg_update.kernel import (
    BLOCK_ROWS, LANES, svrg_update_2d)
from repro.kernels.svrg_update.ref import svrg_update_ref

# the update's name in op metadata and the kernel's name (a device trace's
# `tf_op` path carries it; see repro.core.asysvrg.SCOPES)
SCOPE = "svrg_update"


def apply_leaf(u, g, g0, gf, lr, wd: float = 0.0, interpret: bool = False,
               force_kernel: bool = False):
    mode = kernel_mode(interpret, force_kernel)
    with jax.named_scope(SCOPE):
        if mode == "reference":
            return svrg_update_ref(u, g, g0, gf, lr, wd)
        interpret = mode == "interpret"
        n = u.size
        tile = BLOCK_ROWS * LANES
        rows = -(-n // tile) * BLOCK_ROWS
        pad = rows * LANES - n

        def prep(x):
            return jnp.pad(x.reshape(-1), (0, pad)).reshape(rows, LANES)

        lr_arr = jnp.full((1, 1), lr, jnp.float32)
        out = svrg_update_2d(prep(u), prep(g), prep(g0), prep(gf), lr_arr,
                             wd=wd, interpret=interpret)
        return out.reshape(-1)[:n].reshape(u.shape)


def apply_tree(params, g, g0, gf, lr, wd: float = 0.0,
               interpret: bool = False, force_kernel: bool = False):
    return jax.tree.map(
        lambda u, a, b, c: apply_leaf(u, a, b, c, lr, wd,
                                      interpret=interpret,
                                      force_kernel=force_kernel),
        params, g, g0, gf)
