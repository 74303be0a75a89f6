"""Least bytes any implementation of one host-tick must move through HBM,
from the cell's shapes. The tick does almost no arithmetic per byte (a few
compares and one multiply-add per page), so HBM bandwidth is its bound and
``tick_roofline`` divides this by the peak bandwidth.

The count is the same whatever implements the tick (``batched``,
``pallas``, a fused tick): it counts what the semantics force, not what an
implementation happens to do.
"""
from __future__ import annotations


def host_tick_bytes(ownership: str, n_pages: int, n_tenants: int = 0,
                    slot_pages: int = 0) -> int:
    L = n_pages
    if ownership == "static":
        # inputs, read once: the page's access rate (f32, 4 B) and whether
        # the page is live (bool, 1 B)
        inputs = 5 * L
        # per-page state that must be read: tier (int8, 1 B) to know which
        # pages are candidates and how full each tier is; the owner is a
        # compile-time layout and costs nothing
        read = 1 * L
    else:
        # inputs: the schedule's rates [T, S] f32 and targets [T] int32
        inputs = 4 * n_tenants * slot_pages + 4 * n_tenants
        # state read: tier (1 B) and the owner (int32, 4 B), which changes
        # with the lifecycle and so is state
        read = 5 * L
    # hot, the f32 EWMA, decays on every live page on every tick: it is
    # read and rewritten in full (4 B + 4 B)
    hot = 8 * L
    return inputs + read + hot
