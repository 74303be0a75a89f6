"""Jit'd wrappers for the page-move kernels with impl dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode, pad_up
from repro.kernels.migrate.kernel import commit_moves_tpu, migrate_pages_tpu
from repro.kernels.migrate.ref import commit_moves_ref, migrate_pages_ref


@functools.partial(jax.jit, static_argnames=("impl", "page_block"),
                   donate_argnums=(1,))
def migrate_pages(src_pool, dst_pool, src_idx, dst_idx, sel, *,
                  impl: str = "ref", page_block: int = 8):
    if impl == "ref":
        return migrate_pages_ref(src_pool, dst_pool, src_idx, dst_idx, sel)
    return migrate_pages_tpu(src_pool, dst_pool, src_idx, dst_idx, sel,
                             page_block=page_block,
                             interpret=interpret_mode(impl))


@functools.partial(jax.jit, static_argnames=("direction", "to_tier", "impl"))
def commit_moves(tier, ring_data, head, pages, take, tenants, hot, t, *,
                 direction: int, to_tier: int, impl: str = "ref"):
    """Fused tier scatter + migration-ring append over a compact move
    stream. tier [L] i32; ring_data [C, 5] i32; head scalar i32;
    pages [N] i32 (sentinel L on non-taken lanes is fine); take [N] bool;
    tenants [N] i32; hot [N] f32 (hotness-at-move, ring-bitcast); t scalar
    tick. Returns (tier', ring_data', head') — bit-identical to the
    separate jnp tier ``where`` + ``obs/trace.ring_record``."""
    hot_bits = jax.lax.bitcast_convert_type(hot.astype(jnp.float32),
                                            jnp.int32)
    if impl == "ref":
        return commit_moves_ref(tier, ring_data, head, pages, take, tenants,
                                hot_bits, t, direction=direction,
                                to_tier=to_tier)
    # lane-pad the move stream to a multiple of the kernel's SMEM block:
    # untaken pad lanes are commit no-ops, and the fixed block keeps the
    # tick jaxpr constant across stream sizes
    n, L, C = pages.shape[0], tier.shape[0], ring_data.shape[0]
    nb = min(pad_up(n, 128), 1024)
    pad = -n % nb
    lanes = [jnp.pad(a.astype(jnp.int32), (0, pad))
             for a in (pages, take, tenants, hot_bits)]
    tier_v = jnp.pad(tier.astype(jnp.int32), (0, -L % 1024)).reshape(-1, 8, 128)
    ring_v = jnp.pad(ring_data, ((0, -C % 8), (0, 0))).reshape(-1, 8, 5)
    scal = jnp.stack([t.astype(jnp.int32), head.astype(jnp.int32)])
    tier2, ring2, head2 = commit_moves_tpu(
        tier_v, ring_v, scal, *lanes, n_slots=C, direction=direction,
        to_tier=to_tier, lanes_per_step=nb, interpret=interpret_mode(impl))
    return (tier2.reshape(-1)[:L], ring2.reshape(-1, 5)[:C], head2[0])
