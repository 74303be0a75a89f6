"""Blockwise flash attention for TPU (Pallas): GQA, causal, sliding window.

Layout [B, H, S, D]. Grid = (B, H, nq, nk) with the kv dimension innermost
and "arbitrary" semantics: VMEM scratch (acc, m, l) persists across the nk
iterations of one (b, h, iq) program family; the output block is written on
the last visited kv block. Causal/SWA blocks outside the band are skipped
with @pl.when (zero work on TPU — unlike the XLA reference, which executes
masked blocks).

GQA needs no KV expansion: the kv-head BlockSpec index_map folds h -> h // G,
so each q head streams its own kv head's blocks straight from HBM to VMEM.
MXU alignment: block_q/block_k default 512/512 with D padded to a multiple
of 128 by ops.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               sm_scale: float, causal: bool, window: Optional[int],
               block_q: int, block_k: int, nk: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    q_start = iq * block_q + q_offset          # absolute position of q block
    k_start = ik * block_k

    # band check: does this kv block intersect the visible range?
    q_lo, q_hi = q_start, q_start + block_q - 1
    visible = True
    if causal:
        visible = jnp.asarray(k_start <= q_hi)
    if window is not None:
        visible = jnp.logical_and(visible,
                                  k_start + block_k - 1 > q_lo - window)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
        if causal or window is not None:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), bool)
            if causal:
                mask &= kpos <= qpos
            if window is not None:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(p.astype(v.dtype), v,
                                              (((1,), (0,)), ((), ()))))
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_tpu(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: Optional[int] = None,
                        block_q: int = 512, block_k: int = 512,
                        sm_scale: Optional[float] = None,
                        interpret: bool = False) -> jax.Array:
    """q: [B, H, Sq, D]; k, v: [B, K, Skv, D]. Returns [B, H, Sq, D]."""
    b, h, sq, d = q.shape
    kh, skv = k.shape[1], k.shape[2]
    g = h // kh
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0
    nq, nk = sq // block_q, skv // block_k
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    q_offset = skv - sq                      # right-aligned queries

    kernel = functools.partial(
        _fa_kernel, sm_scale=sm_scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk, q_offset=q_offset)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
