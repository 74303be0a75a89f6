"""Mamba2 SSD chunked-scan kernel (Pallas TPU).

Grid = (B, H, n_chunks) with the chunk dimension innermost ("arbitrary"):
the inter-chunk SSM state h [P, N] lives in VMEM scratch and carries the
recurrence; each program computes one chunk's intra-chunk (dual, quadratic)
term and the state contribution — the two matmuls hit the MXU with
[Q, N] x [N, P] shapes. Chunk length Q defaults to 128/256: Q x Q decay
matrix and Q x max(N, P) operands stay comfortably inside VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_s, *,
                nc: int, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_s[...] = jnp.zeros_like(h_s)

    x = x_ref[0, 0].astype(jnp.float32)          # [Q, P]
    a = a_ref[0, 0].astype(jnp.float32)          # [Q]
    b = b_ref[0, 0].astype(jnp.float32)          # [Q, N]
    c = c_ref[0, 0].astype(jnp.float32)          # [Q, N]

    a_cum = jnp.cumsum(a)                        # [Q]
    # L[i,j] = exp(sum_{k=j+1..i} a_k) for i >= j
    diff = a_cum[:, None] - a_cum[None, :]
    q = a.shape[0]
    tri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(tri, jnp.exp(diff), 0.0)
    # intra-chunk: y_diag = ((C B^T) * L) X
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ()))) * L  # [Q,Q]
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())))      # [Q,P]
    # inter-chunk: y_off = (C h^T) * exp(a_cum)
    h = h_s[...]                                                      # [P,N]
    y += jax.lax.dot_general(c, h, (((1,), (1,)), ((), ()))) \
        * jnp.exp(a_cum)[:, None]
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update: h' = exp(A_chunk) h + X^T (B * decay)
    decay_states = jnp.exp(a_cum[-1] - a_cum)                          # [Q]
    contrib = jax.lax.dot_general(x, b * decay_states[:, None],
                                  (((0,), (0,)), ((), ())))            # [P,N]
    h_s[...] = jnp.exp(a_cum[-1]) * h + contrib

    @pl.when(ic == nc - 1)
    def _fin():
        hout_ref[0, 0] = h_s[...]


def ssd_scan_tpu(x, a, b, c, *, chunk: int = 128, interpret: bool = False):
    """x: [B,S,H,P]; a: [B,S,H]; b,c: [B,S,H,N] (groups pre-broadcast).
    Returns (y [B,S,H,P], h_final [B,H,P,N])."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    # layout: [B, H, S, *] so the chunk dim tiles cleanly
    xt = x.transpose(0, 2, 1, 3)
    at = a.transpose(0, 2, 1)
    bt = b.transpose(0, 2, 1, 3)
    ct = c.transpose(0, 2, 1, 3)

    kernel = functools.partial(_ssd_kernel, nc=nc, chunk=chunk)
    y, hf = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, chunk), lambda ib, ih, ic: (ib, ih, ic)),
            pl.BlockSpec((1, 1, chunk, N), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda ib, ih, ic: (ib, ih, ic, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, chunk, P), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, P, N), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, at, bt, ct)
    return y.transpose(0, 2, 1, 3), hf
