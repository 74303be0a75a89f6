"""Jit'd wrappers for the selection-core kernels with impl dispatch.

``impl`` ∈ {"ref", "pallas", "pallas_interpret"}: "ref" is the jnp oracle
(and the compiled fast path on backends without a Mosaic lowering),
"pallas" lowers to TPU, "pallas_interpret" runs the same kernel on the
Pallas interpreter (the CI equivalence gate).

The wrappers own the tile-alignment contract: rows are padded to a
multiple of ``block_rows`` (quota 0, all-invalid) and columns to a
multiple of 128 (the TPU lane width); outputs are sliced back and the
kernel's padded-width sentinel is renormalized to the logical ``S``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode, pad_up
from repro.kernels.select.kernel import (seg_reduce_tpu, seg_sums_tpu,
                                         seg_topk_tpu)
from repro.kernels.select.ref import (seg_reduce_ref, seg_sums_ref,
                                      seg_topk_ref)

_LANE = 128
_MAX_COLS = 4096   # seg_topk column tile: [8, 4096] f32 = 128 KiB of VMEM


def _col_block(sp: int) -> int:
    """Widest lane-aligned divisor of the padded row width up to
    ``_MAX_COLS``: one tile for tenant rows that fit, a column walk for
    the dynamic strategy's whole-pool rows (S = L)."""
    n = sp // _LANE
    return _LANE * max(d for d in range(1, _MAX_COLS // _LANE + 1)
                       if n % d == 0)


def _pad_rows(score, valid, block_rows):
    T, S = score.shape
    Tp, Sp = pad_up(T, block_rows), pad_up(S, _LANE)
    if (Tp, Sp) == (T, S):
        return score, valid
    score = jnp.pad(score, ((0, Tp - T), (0, Sp - S)))
    valid = jnp.pad(valid, ((0, Tp - T), (0, Sp - S)))
    return score, valid


@functools.partial(jax.jit, static_argnames=("k", "impl", "block_rows"))
def seg_topk(score, valid, quotas, k: int, *, impl: str = "ref",
             block_rows: int = 8):
    """Per-row quota-bounded top-k. score/valid: [T, S]; quotas: [T].
    Returns (cols [T, k] i32 — sentinel S on non-taken lanes,
    take [T, k] bool, counts [T] i32)."""
    T, S = score.shape
    k = max(min(k, S), 1)
    score = score.astype(jnp.float32)
    if impl == "ref":
        return seg_topk_ref(score, valid, quotas, k)
    elig = (valid & jnp.isfinite(score)).astype(jnp.int32)
    score_p, elig_p = _pad_rows(score, elig, block_rows)
    Tp = score_p.shape[0]
    q = jnp.zeros((Tp, 1), jnp.int32).at[:T, 0].set(quotas.astype(jnp.int32))
    cols, take, counts = seg_topk_tpu(
        score_p, elig_p, q, k=k, block_rows=block_rows,
        block_cols=_col_block(score_p.shape[1]), interpret=interpret_mode(impl))
    # padded-width sentinel (Sp) -> logical sentinel (S); real cols are < S
    cols = jnp.minimum(cols[:T], S)
    return cols, take[:T].astype(bool), counts[:T, 0]


@functools.partial(jax.jit, static_argnames=("impl", "block_rows"))
def seg_reduce(x, valid, *, impl: str = "ref", block_rows: int = 8):
    """Fused per-row sum + exclusive prefix sum (integers only).
    x/valid: [T, S]. Returns (sums [T] i32, prefix [T, S] i32)."""
    T, S = x.shape
    x = x.astype(jnp.int32)
    if impl == "ref":
        return seg_reduce_ref(x, valid)
    x_p, valid_p = _pad_rows(x, valid.astype(jnp.int32), block_rows)
    sums, pre = seg_reduce_tpu(x_p, valid_p, block_rows=block_rows,
                               interpret=interpret_mode(impl))
    return sums[:T, 0], pre[:T, :S]


@functools.partial(jax.jit, static_argnames=("impl", "block_rows"))
def seg_sums(x, valid, *, impl: str = "ref", block_rows: int = 8):
    """Per-row masked sum (integers only). x/valid: [T, S] -> [T] i32."""
    T, S = x.shape
    x = x.astype(jnp.int32)
    if impl == "ref":
        return seg_sums_ref(x, valid)
    x_p, valid_p = _pad_rows(x, valid.astype(jnp.int32), block_rows)
    sums = seg_sums_tpu(x_p, valid_p, block_rows=block_rows,
                        interpret=interpret_mode(impl))
    return sums[:T, 0]
