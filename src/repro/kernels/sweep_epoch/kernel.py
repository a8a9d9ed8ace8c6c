"""Pallas sweep-epoch megakernel: one launch per (group × run).

Why a kernel: the vmap engine dispatches the inner minibatch scan as
per-update XLA ops, so every one of the M̃·epochs updates streams the
iterate ``w``, the snapshot ``w̃`` (u0), the full-gradient anchor ``μ`` and
the delay ring buffer through HBM — the scan carry alone is
(buf_len + 2)·d floats read AND written per update. The paper's whole
argument is that the AsySVRG inner loop is cheap; fused, it is: this kernel
maps the config-row axis of a sweep group onto the Pallas grid and runs the
ENTIRE multi-epoch scan for one row inside a single kernel invocation, so
``w``, ``w̃``, ``μ`` and the ring buffer stay resident in VMEM for the whole
epoch and only the sampled data rows move. A merged service group is ONE
megakernel launch instead of M̃·epochs·rows op dispatches.

The kernel body executes the SAME per-row epochs-scan functions the vmap
engine batches (`repro.core.asysvrg._asysvrg_epochs_core` /
`repro.core.hogwild._hogwild_epochs_core`): under the Pallas interpreter
the body lowers to the identical XLA:CPU ops per row, and the engine's
vmap-bitwise-stable contract (vmap == per-row bits) closes the loop — the
fused path is BIT-IDENTICAL to the vmap path in interpret mode
(tests/test_kernel_sweep.py). Mosaic refuses the compiled lowering for
TPU (the (1, 2) key row block is not (8, 128)-aligned, and the full data
blocks put the dataset in VMEM), so a TPU backend rejects the fused engine
at plan time (`repro.kernels.dispatch.FUSED_TPU_BLOCKERS`, ROADMAP S6).

Operand layout (built by `repro.kernels.sweep_epoch.ops`):

  * objective data args — full-array blocks, identical for every grid step
    (the index map is constant, so Pallas keeps them resident across rows);
    0-d scalars are lifted to (1, 1).
  * per-row arrays — row-blocked: scalar rows [C] are lifted to [C, 1] and
    blocked (1, 1); the PRNG key rows [C, 2] and the w0 rows [C, d] are
    blocked (1, ...) over the grid axis.
  * outputs — final iterates [C, d] and loss histories [C, epochs+1],
    row-blocked the same way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _const_index_map(ndim: int):
    return lambda i: (0,) * ndim


def _row_index_map(ndim: int):
    return lambda i: (i,) + (0,) * (ndim - 1)


def sweep_epoch_call(row_fn, data, row_args, *, epochs: int, dim: int,
                     interpret: bool):
    """Launch ``row_fn`` over the config-row grid in ONE `pallas_call`.

    ``row_fn(data, *row_scalars) -> (w_fin [dim], losses [epochs+1])`` is
    the per-row epochs scan; ``data`` is the objective's `data_args` tuple
    (any shapes, replicated across rows) and ``row_args`` the row-leading
    arrays — every 1-D entry is treated as a scalar row, higher-rank
    entries ([C, 2] keys, [C, dim] w0) pass their per-row slice through.

    Returns (w_fin [C, dim], losses [C, epochs+1]).
    """
    rows = int(row_args[0].shape[0])

    # -- pack operands: lift 0-d data scalars and 1-d row arrays to 2-d ----
    data_ops, data_specs, data_scalar = [], [], []
    for arr in data:
        arr = jnp.asarray(arr)
        scalar = arr.ndim == 0
        if scalar:
            arr = arr.reshape(1, 1)
        data_ops.append(arr)
        data_scalar.append(scalar)
        data_specs.append(pl.BlockSpec(arr.shape,
                                       _const_index_map(arr.ndim)))

    row_ops, row_specs, row_scalar = [], [], []
    for arr in row_args:
        arr = jnp.asarray(arr)
        scalar = arr.ndim == 1
        if scalar:
            arr = arr[:, None]
        row_ops.append(arr)
        row_scalar.append(scalar)
        row_specs.append(pl.BlockSpec((1,) + arr.shape[1:],
                                      _row_index_map(arr.ndim)))

    w_dtype = row_ops[-1].dtype                 # w0 rows define the iterate

    def kernel(*refs):
        d_refs = refs[:len(data_ops)]
        r_refs = refs[len(data_ops):len(data_ops) + len(row_ops)]
        w_ref, hist_ref = refs[-2:]
        data_vals = tuple(r[0, 0] if s else r[...]
                          for r, s in zip(d_refs, data_scalar))
        row_vals = tuple(r[0, 0] if s else r[0]
                         for r, s in zip(r_refs, row_scalar))
        w_fin, losses = row_fn(data_vals, *row_vals)
        w_ref[0] = w_fin
        hist_ref[0] = losses

    return pl.pallas_call(
        kernel,
        grid=(rows,),
        in_specs=data_specs + row_specs,
        out_specs=[
            pl.BlockSpec((1, dim), _row_index_map(2)),
            pl.BlockSpec((1, epochs + 1), _row_index_map(2)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, dim), w_dtype),
            jax.ShapeDtypeStruct((rows, epochs + 1), jnp.float32),
        ],
        # rows are independent: the grid axis is embarrassingly parallel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*data_ops, *row_ops)
