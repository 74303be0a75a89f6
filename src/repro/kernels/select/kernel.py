"""Selection-core Pallas TPU kernels: tiled segmented top-k + reductions.

The kernels tile the [T, S] tenant-row space over row blocks of
``block_rows``, parallel across tenants. ``seg_reduce``/``seg_sums`` hold
one [block_rows, S] tile; ``seg_topk`` also walks the columns in
[block_rows, block_cols] tiles with a running top-k, so its VMEM use is
bounded by the tile, not by S (the dynamic strategy's rows are S = L).

``seg_topk`` fuses the per-tenant masking, scoring and quota-bounded
selection that the jnp path spreads across a gather, a masked ``top_k``
and a take-compare: one pass of iterative max-extraction per tile. The
extraction loop runs ``min(max(quota), k)`` rounds — the *quota* bound, not
the row width — and each round is a row-max + row-argmin over the tile
(pure VPU work, no sort network, no cross-program traffic). Ties break as
(score desc, column asc), bit-matching ``jax.lax.top_k``'s "lower index
wins".

``seg_reduce`` replaces the length-L cumsum + boundary gathers of
``allocation_ranks_contiguous``/``by_tenant_contiguous`` with a per-row
Hillis-Steele log-shift scan: log2(S) shifted adds per tile, emitting the
per-row total and the exclusive prefix in one pass. Integer-only: integer
addition is associative so the reordered reduction is bit-equal to the jnp
cumsum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ------------------------------------------------------------- seg_topk ----
def _seg_topk_kernel(score_ref, valid_ref, quota_ref, cols_ref, take_ref,
                     cnt_ref, val_ref, *, k: int, S: int):
    """One [Bt, Sc] column block of a row block. The row's running top-k
    (values in ``val_ref``, columns in ``cols_ref``) is merged with the
    block by the same max-extraction; earlier blocks hold lower columns,
    so the (score desc, column asc) order is the whole row's."""
    j = pl.program_id(1)
    s = jnp.where(valid_ref[...] != 0, score_ref[...], -jnp.inf)  # [Bt, Sc]
    Bt, Sc = s.shape
    q = quota_ref[...][:, 0]                                      # [Bt] i32
    col = j * Sc + jax.lax.broadcasted_iota(jnp.int32, (Bt, Sc), 1)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (Bt, k), 1)

    @pl.when(j == 0)
    def _init():
        val_ref[...] = jnp.full((Bt, k), -jnp.inf, jnp.float32)
        cols_ref[...] = jnp.full((Bt, k), S, jnp.int32)

    # Quota-bounded round count: lanes past a row's own quota are dropped
    # at the end, so every row keeps the block's best ``rounds``.
    rounds = jnp.minimum(jnp.max(jnp.maximum(q, 0)), k)

    def round_(r, carry):
        s, cv, cc, nv, nc = carry
        m = jnp.maximum(jnp.max(s, axis=1), jnp.max(cv, axis=1))   # [Bt]
        c = jnp.minimum(                                   # lowest max col
            jnp.min(jnp.where(cv == m[:, None], cc, S), axis=1),
            jnp.min(jnp.where(s == m[:, None], col, S), axis=1))
        commit = (lane_iota == r) & (m > -jnp.inf)[:, None]
        nv = jnp.where(commit, m[:, None], nv)
        nc = jnp.where(commit, c[:, None], nc)
        s = jnp.where(col == c[:, None], -jnp.inf, s)       # consume winner
        cv = jnp.where(cc == c[:, None], -jnp.inf, cv)
        return s, cv, cc, nv, nc

    nv0 = jnp.full((Bt, k), -jnp.inf, jnp.float32)
    nc0 = jnp.full((Bt, k), S, jnp.int32)
    _, _, _, nv, nc = jax.lax.fori_loop(
        0, rounds, round_, (s, val_ref[...], cols_ref[...], nv0, nc0))
    val_ref[...] = nv
    cols_ref[...] = nc

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        take = (lane_iota < q[:, None]) & (nv > -jnp.inf)
        cols_ref[...] = jnp.where(take, nc, S)
        take_ref[...] = take.astype(jnp.int32)
        cnt_ref[...] = take.astype(jnp.int32).sum(axis=1,
                                                  dtype=jnp.int32)[:, None]


def seg_topk_tpu(score, valid, quotas, *, k: int, block_rows: int = 8,
                 block_cols: int = 128, interpret: bool = False):
    """score [T, S] f32, valid [T, S] int32, quotas [T, 1] int32; T must be
    a multiple of ``block_rows`` and S of ``block_cols`` (ops wrapper pads).
    The grid walks row blocks in parallel and column blocks in order, so
    VMEM holds one [block_rows, block_cols] tile per input at any S.
    Returns (cols [T, k] i32 with sentinel S, take [T, k] i32,
    counts [T, 1] i32)."""
    T, S = score.shape
    Bt, Sc = block_rows, block_cols
    return pl.pallas_call(
        functools.partial(_seg_topk_kernel, k=k, S=S),
        grid=(T // Bt, S // Sc),
        in_specs=[
            pl.BlockSpec((Bt, Sc), lambda i, j: (i, j)),
            pl.BlockSpec((Bt, Sc), lambda i, j: (i, j)),
            pl.BlockSpec((Bt, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((Bt, k), lambda i, j: (i, 0)),
            pl.BlockSpec((Bt, k), lambda i, j: (i, 0)),
            pl.BlockSpec((Bt, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, k), jnp.int32),
            jax.ShapeDtypeStruct((T, k), jnp.int32),
            jax.ShapeDtypeStruct((T, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((Bt, k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(score, valid, quotas)


# ----------------------------------------------------------- seg_reduce ----
def _row_scan(x):
    """Inclusive prefix sum along axis 1 (log-shift adds, int32)."""
    S = x.shape[1]
    inc = x
    off = 1
    while off < S:
        shifted = jnp.concatenate(
            [jnp.zeros((x.shape[0], off), jnp.int32), inc[:, :-off]], axis=1)
        inc = inc + shifted
        off *= 2
    return inc


def _seg_reduce_kernel(x_ref, valid_ref, sum_ref, pre_ref):
    x = jnp.where(valid_ref[...] != 0, x_ref[...], 0)
    inc = _row_scan(x)
    sum_ref[...] = inc[:, -1:]
    pre_ref[...] = inc - x


def _seg_sums_kernel(x_ref, valid_ref, sum_ref):
    x = jnp.where(valid_ref[...] != 0, x_ref[...], 0)
    sum_ref[...] = x.sum(axis=1, dtype=jnp.int32)[:, None]


def seg_reduce_tpu(x, valid, *, block_rows: int = 8,
                   interpret: bool = False):
    """x/valid [T, S] int32, T a multiple of ``block_rows``. Returns
    (sums [T, 1] i32, prefix [T, S] i32)."""
    T, S = x.shape
    Bt = block_rows
    return pl.pallas_call(
        _seg_reduce_kernel,
        grid=(T // Bt,),
        in_specs=[
            pl.BlockSpec((Bt, S), lambda i: (i, 0)),
            pl.BlockSpec((Bt, S), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((Bt, 1), lambda i: (i, 0)),
            pl.BlockSpec((Bt, S), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, 1), jnp.int32),
            jax.ShapeDtypeStruct((T, S), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, valid)


def seg_sums_tpu(x, valid, *, block_rows: int = 8,
                 interpret: bool = False):
    """Sum-only variant (skips the [T, S] prefix write for by_tenant)."""
    T, S = x.shape
    Bt = block_rows
    return pl.pallas_call(
        _seg_sums_kernel,
        grid=(T // Bt,),
        in_specs=[
            pl.BlockSpec((Bt, S), lambda i: (i, 0)),
            pl.BlockSpec((Bt, S), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((Bt, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, valid)
