"""Page-migration kernels (Pallas TPU).

``migrate_pages_tpu`` — batched promote/demote page copies for the KV
pools: the scalar-prefetch page table (src_idx, dst_idx, sel) drives the
BlockSpec index maps, so the DMA engine streams exactly the selected
[page_block·pt, K, D] slab per (layer-block, sequence) program, nothing
else. The destination pool is input/output-aliased so unselected sequences
keep their data without any copy. On real hardware this is the HBM<->host
(CXL-analogue) transfer; the same kernel covers both directions. The layer
axis is tiled by ``page_block`` (not the seed's hardcoded single-layer
blocks) so the grid is L/page_block × B instead of L × B — at real batch
sizes the per-program dispatch overhead dominated the copy itself.

``commit_moves_tpu`` — the tiering tick's fused move commit: one kernel
pass applies the promotion/demotion scatter to the [L] tier vector AND
appends the packed migration-ring events, replacing a drop-mode scatter
plus the five-column ring build/scatter of ``obs/trace.ring_record``. The
compact [N = T·k] lane stream walks through SMEM one block per grid step,
and each taken lane commits in lane order: a masked store into the
[8, 128] tier tile that holds its page, and one into the ring tile of the
next slot. Writing slots in lane order keeps the newest event of a slot
written twice, which is exactly the jnp ring's newest-C-wins window.
``tier`` and ``ring_data`` are input/output-aliased: the commit is
in-place, the way a real migration engine retires a move queue.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mig_kernel(src_idx_ref, dst_idx_ref, sel_ref, src_ref, dst_in_ref,
                dst_ref):
    b = pl.program_id(1)

    @pl.when(sel_ref[b] != 0)
    def _copy():
        dst_ref[...] = src_ref[...]

    @pl.when(sel_ref[b] == 0)
    def _keep():
        dst_ref[...] = dst_in_ref[...]


def migrate_pages_tpu(src_pool, dst_pool, src_idx, dst_idx, sel, *,
                      page_block: int = 8, interpret: bool = False):
    """src/dst_pool: [L, B, Mp, pt, K, D]; src_idx/dst_idx: [B]; sel: [B].

    ``page_block`` layers are copied per program (clamped down to a divisor
    of L), amortizing grid dispatch over an 8x larger DMA slab by default.
    """
    L, B, Ms_, pt, K, D = src_pool.shape
    Md = dst_pool.shape[2]
    pb = max(min(page_block, L), 1)
    while L % pb:
        pb -= 1

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(L // pb, B),
        in_specs=[
            pl.BlockSpec((pb, 1, 1, pt, K, D),
                         lambda l, b, si, di, se: (l, b, si[b], 0, 0, 0)),
            pl.BlockSpec((pb, 1, 1, pt, K, D),
                         lambda l, b, si, di, se: (l, b, di[b], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((pb, 1, 1, pt, K, D),
                               lambda l, b, si, di, se: (l, b, di[b], 0, 0, 0)),
    )
    return pl.pallas_call(
        _mig_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst_pool.shape, dst_pool.dtype),
        input_output_aliases={4: 0},   # dst_pool (3 scalars + src = idx 4) -> out
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.maximum(src_idx, 0), jnp.maximum(dst_idx, 0),
      sel.astype(jnp.int32), src_pool, dst_pool)


# ------------------------------------------------------- commit_moves ------
def _moves_kernel(pages_ref, take_ref, ten_ref, hot_ref, scal_ref,
                  tier_in_ref, ring_in_ref, tier_ref, ring_ref, head_ref,
                  cnt_ref, *, direction: int, to_tier: int, n_slots: int):
    """One grid step commits one SMEM block of the lane stream. The tier
    vector is a [L/1024, 8, 128] view and the ring a [C/8, 8, 5] view, so
    each store is a masked update of one tile picked by a leading-axis
    index. Ring slots follow the lane order: a slot written twice keeps
    its newest event, which is exactly the newest-C-wins window."""
    step = pl.program_id(0)
    t, head = scal_ref[0], scal_ref[1]

    @pl.when(step == 0)
    def _init():
        tier_ref[...] = tier_in_ref[...]
        ring_ref[...] = ring_in_ref[...]
        cnt_ref[0] = 0

    tile = tier_ref.shape[1:]
    sub = jax.lax.broadcasted_iota(jnp.int32, tile, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, tile, 1)
    rsub = jax.lax.broadcasted_iota(jnp.int32, ring_ref.shape[1:], 0)
    rcol = jax.lax.broadcasted_iota(jnp.int32, ring_ref.shape[1:], 1)

    def commit(j, cnt):
        taken = take_ref[j] != 0

        @pl.when(taken)
        def _store():
            page = pages_ref[j]
            blk, off = page // 1024, page % 1024
            tier_ref[blk] = jnp.where((sub == off // 128) & (lane == off % 128),
                                      to_tier, tier_ref[blk])
            slot = (head + cnt) % n_slots
            row = jnp.where(rcol == 0, t, jnp.where(
                rcol == 1, ten_ref[j], jnp.where(
                    rcol == 2, page, jnp.where(rcol == 3, direction,
                                               hot_ref[j]))))
            ring_ref[slot // 8] = jnp.where(rsub == slot % 8, row,
                                            ring_ref[slot // 8])
        return cnt + taken.astype(jnp.int32)

    cnt = jax.lax.fori_loop(0, pages_ref.shape[0], commit, cnt_ref[0])
    cnt_ref[0] = cnt
    head_ref[0] = head + cnt


def commit_moves_tpu(tier, ring_data, scal, pages, take, tenants, hot_bits,
                     *, n_slots: int, direction: int, to_tier: int,
                     lanes_per_step: int = 1024, interpret: bool = False):
    """tier [L/1024, 8, 128] i32; ring_data [C/8, 8, 5] i32 (``n_slots``
    real rows); scal [2] i32 = (tick, head); pages/take/tenants/hot_bits
    [N] i32 with N a multiple of ``lanes_per_step``. The lane stream walks
    through SMEM one block per grid step while the tier and ring views stay
    resident in VMEM. Returns (tier', ring_data', head' [1])."""
    N = pages.shape[0]
    nb = lanes_per_step
    lanes = pl.BlockSpec((nb,), lambda i: (i,), memory_space=pltpu.SMEM)
    whole = pl.BlockSpec(memory_space=pltpu.SMEM)
    tier_spec = pl.BlockSpec(tier.shape, lambda i: (0, 0, 0))
    ring_spec = pl.BlockSpec(ring_data.shape, lambda i: (0, 0, 0))
    return pl.pallas_call(
        functools.partial(_moves_kernel, direction=direction,
                          to_tier=to_tier, n_slots=n_slots),
        grid=(N // nb,),
        in_specs=[lanes, lanes, lanes, lanes, whole, tier_spec, ring_spec],
        out_specs=[tier_spec, ring_spec, whole],
        out_shape=[
            jax.ShapeDtypeStruct(tier.shape, jnp.int32),
            jax.ShapeDtypeStruct(ring_data.shape, jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        input_output_aliases={5: 0, 6: 1},   # tier, ring_data -> in place
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pages, take, tenants, hot_bits, scal, tier, ring_data)
