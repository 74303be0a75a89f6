"""Tiered paged-attention decode kernel (Pallas TPU) — the paper's hot path.

One invocation computes the pool-partial attention of a single tier's page
pool (fast = HBM pages, slow = CXL/host-class pages; on real hardware the
slow pool ref lives in pinned_host memory and Mosaic streams it via DMA).
Each (b, block) program:
  * loads `page_block` pages [page_block*pt tokens, K, D] into VMEM,
  * computes masked scores for all H = K*G query heads (GQA by static K
    loop — no KV expansion, each kv head read once),
  * online-softmax accumulates (acc, m, l) in VMEM scratch,
  * emits the per-page attention mass — the paper's hotness signal ("NUMA
    hint faults" == softmax weights) — with a per-block stabilizer so ops.py
    can renormalize exactly.

Grid = (B, nblk), nblk innermost/"arbitrary": scratch persists, outputs
(acc, m, l) written on the last block.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(seq_ref, q_ref, k_ref, v_ref, page_ref,
            acc_ref, m_ref, l_ref, mass_ref, mstab_ref,
            acc_s, m_s, l_s, *,
            sm_scale: float, window: Optional[int], K: int, G: int,
            pt: int, page_block: int, nblk: int):
    ib = pl.program_id(1)
    H = K * G
    T = page_block * pt

    @pl.when(ib == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0].astype(jnp.float32) * sm_scale            # [H, D]
    kblk = k_ref[0].astype(jnp.float32)                    # [page_block, pt, K, D]
    vblk = v_ref[0].astype(jnp.float32)
    pages = page_ref[0]                                    # [page_block] int32
    seq = seq_ref[0]

    kf = kblk.reshape(T, K, -1)
    vf = vblk.reshape(T, K, -1)

    # scores for all heads, kv-head at a time (GQA without expansion)
    s_rows = []
    for kk in range(K):
        qk = q.reshape(K, G, -1)[kk]                       # [G, D]
        s_rows.append(jax.lax.dot_general(
            qk, kf[:, kk, :], (((1,), (1,)), ((), ()))))   # [G, T]
    s = jnp.concatenate(s_rows, axis=0)                    # [H, T]

    # validity: absolute token id from the page's absolute page number
    tok = (pages.astype(jnp.int32) * pt)[:, None] + jax.lax.broadcasted_iota(
        jnp.int32, (page_block, pt), 1)
    ok = (pages >= 0)[:, None] & (tok <= seq)
    if window is not None:
        ok &= tok > (seq - window)
    okf = ok.reshape(1, T)
    s = jnp.where(okf, s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))             # [H]
    p = jnp.where(okf, jnp.exp(s - m_new[:, None]), 0.0)   # [H, T]
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + p.sum(axis=1)
    # acc update per kv-head (GQA mapping exact, each kv head read once)
    pvs = []
    for kk in range(K):
        pvs.append(jax.lax.dot_general(
            p[kk * G:(kk + 1) * G], vf[:, kk, :],
            (((1,), (0,)), ((), ()))))                     # [G, D]
    acc_s[...] = acc_s[...] * corr[:, None] + jnp.concatenate(pvs, axis=0)
    m_s[...] = m_new

    # per-page mass with this block's stabilizer (renormalized in ops.py)
    mass_ref[0] = p.reshape(H, page_block, pt).sum(axis=2)  # [H, page_block]
    mstab_ref[0] = m_new[:, None]                           # [H, 1]

    @pl.when(ib == nblk - 1)
    def _finalize():
        acc_ref[0] = acc_s[...]
        m_ref[0] = m_s[...]
        l_ref[0] = l_s[...]


def pool_attention_partial_tpu(q, pool_k, pool_v, slot_page, seq_len, *,
                               window: Optional[int] = None,
                               sm_scale: Optional[float] = None,
                               page_block: int = 8,
                               interpret: bool = False):
    """q: [B,H,D]; pool_k/v: [B,Mp,pt,K,D]; slot_page: [B,Mp]; seq_len: [B].

    Returns (acc [B,H,D] f32, m [B,H], l [B,H], mass [B,H,Mp] — mass carries
    a per-block stabilizer, also returned: mstab [B,H,nblk])."""
    B, Mp, pt, K, D = pool_k.shape
    H = q.shape[1]
    G = H // K
    page_block = min(page_block, Mp)
    assert Mp % page_block == 0
    nblk = Mp // page_block
    scale = sm_scale if sm_scale is not None else 1.0 / (D ** 0.5)

    kernel = functools.partial(
        _kernel, sm_scale=scale, window=window, K=K, G=G, pt=pt,
        page_block=page_block, nblk=nblk)

    out_shapes = (
        jax.ShapeDtypeStruct((B, H, D), jnp.float32),       # acc
        jax.ShapeDtypeStruct((B, H), jnp.float32),          # m
        jax.ShapeDtypeStruct((B, H), jnp.float32),          # l
        jax.ShapeDtypeStruct((B, H, Mp), jnp.float32),      # mass
        jax.ShapeDtypeStruct((B, H, nblk), jnp.float32),    # mstab
    )
    acc, m, l, mass, mstab = pl.pallas_call(
        kernel,
        grid=(B, nblk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, ib: (b,)),                   # seq_len
            pl.BlockSpec((1, H, D), lambda b, ib: (b, 0, 0)),         # q
            pl.BlockSpec((1, page_block, pt, K, D),
                         lambda b, ib: (b, ib, 0, 0, 0)),             # k
            pl.BlockSpec((1, page_block, pt, K, D),
                         lambda b, ib: (b, ib, 0, 0, 0)),             # v
            pl.BlockSpec((1, page_block), lambda b, ib: (b, ib)),     # pages
        ],
        out_specs=(
            pl.BlockSpec((1, H, D), lambda b, ib: (b, 0, 0)),
            pl.BlockSpec((1, H), lambda b, ib: (b, 0)),
            pl.BlockSpec((1, H), lambda b, ib: (b, 0)),
            pl.BlockSpec((1, H, page_block), lambda b, ib: (b, 0, ib)),
            pl.BlockSpec((1, H, 1), lambda b, ib: (b, 0, ib)),
        ),
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(seq_len, q, pool_k, pool_v, slot_page)
    return acc, m, l, mass, mstab
