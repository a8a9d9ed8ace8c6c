"""Pallas TPU flash-attention (prefill) kernel.

TPU adaptation of the flash algorithm (no warps / shared-memory banking —
VMEM block streaming + online softmax instead):

  grid = (B*H, nq, nk) with the kv axis innermost and SEQUENTIAL
  ("arbitrary" dimension semantics): the kernel carries the running max m,
  normalizer l and output accumulator across kv blocks in VMEM scratch,
  rescaling on each new block (the standard online-softmax recurrence).
  Causal/windowed masking is computed from block indices; fully-masked
  kv blocks are skipped via pl.when (the causal lower-triangle saves ~2x).

Block sizes default to (128, 512): q-tile 128 rows aligns the MXU; the kv
tile bounds VMEM at ~ (128·d + 512·d·2 + 128·512) · 4B ≈ 1.3 MiB for d=128.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 512
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  bq: int, bk: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk
    # skip blocks that are fully masked (above the causal diagonal /
    # left of the local window)
    relevant = True
    if causal:
        relevant = k_start <= q_start + bq - 1
    if window > 0:
        relevant = jnp.logical_and(
            relevant, k_start + bk - 1 >= q_start - window + 1)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # [bq, d]
        k = k_ref[0].astype(jnp.float32)              # [bk, d]
        v = v_ref[0].astype(jnp.float32)              # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        pos_q = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        pos_k = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), bool)
        if causal:
            ok &= pos_q >= pos_k
        if window > 0:
            ok &= (pos_q - pos_k) < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]                           # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                        # [bq, bk]
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l_sum = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l_sum).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = False):
    """q,k,v [BH, S, d] -> [BH, S, d]. S % max(bq,bk) == 0."""
    BH, S, d = q.shape
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk
    scale = 1.0 / math.sqrt(d)

    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max m
            pltpu.VMEM((bq, 1), jnp.float32),     # running normalizer l
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
