"""Fixed-capacity in-graph migration event ring buffer.

Every promotion/demotion executed by the engine tick or the KV tiering step
appends a (tick, tenant, page, direction, hotness-at-move) record. Records
are packed into ONE [capacity, 5] int32 buffer (hotness bit-cast), so an
append is a single scatter over the source lanes instead of five. On one
TPU v5e the tick's whole ``commit`` stage, ring append included, is 4.8%
of its device time on the 64-tenant stacked host and 26.7% on the churned
one (``bench/stages.py``; PERF.md section 5). Recording is
branch-free (``mode="drop"`` discards unselected lanes) and works under
jit, scan and vmap; the newest ``capacity`` events survive, older ones are
overwritten — exactly a kernel trace ring. ``decode_ring`` converts the
on-device ring to structured numpy records host-side.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

DIR_PROMOTE = 0
DIR_DEMOTE = 1

# packed column order in MigrationRing.data
COL_TICK, COL_TENANT, COL_PAGE, COL_DIR, COL_HOT = range(5)

EVENT_DTYPE = np.dtype([("tick", np.int32), ("tenant", np.int32),
                        ("page", np.int32), ("direction", np.int32),
                        ("hotness", np.float32)])


class MigrationRing(NamedTuple):
    data: jax.Array       # [C, 5] int32: tick, tenant, page, direction,
    #                       hotness (f32 bit-cast); tick = -1 = never written
    head: jax.Array       # scalar int32: total events ever recorded


def init_ring(capacity: int) -> MigrationRing:
    data = jnp.zeros((capacity, 5), jnp.int32).at[:, COL_TICK].set(-1)
    return MigrationRing(data=data, head=jnp.zeros((), jnp.int32))


def ring_record(ring: MigrationRing, mask: jax.Array, pages: jax.Array,
                tenants: jax.Array, hotness: jax.Array, direction: int,
                t: jax.Array) -> MigrationRing:
    """Append all events where ``mask`` is set. mask/pages/tenants/hotness
    share one shape (any rank); events land oldest-first at head..head+n."""
    C = ring.data.shape[0]
    m = mask.reshape(-1)
    offs = jnp.cumsum(m.astype(jnp.int32)) - 1          # slot among selected
    total = offs[-1] + 1 if m.shape[0] else jnp.zeros((), jnp.int32)
    # if one call selects more than C events, keep only the newest C — the
    # window of C consecutive offsets keeps scatter indices unique (a
    # duplicate-index set has an unspecified winner in XLA)
    keep = m & (offs >= total - C)
    idx = jnp.where(keep, (ring.head + offs) % C, C)    # C = OOB -> dropped
    rows = jnp.stack([
        jnp.broadcast_to(t, m.shape).astype(jnp.int32),
        tenants.reshape(-1).astype(jnp.int32),
        pages.reshape(-1).astype(jnp.int32),
        jnp.full(m.shape, direction, jnp.int32),
        jax.lax.bitcast_convert_type(
            hotness.reshape(-1).astype(jnp.float32), jnp.int32),
    ], axis=-1)                                         # [L, 5]
    return MigrationRing(
        data=ring.data.at[idx].set(rows, mode="drop"),
        head=ring.head + m.sum())


def decode_ring(ring: MigrationRing) -> tuple[np.ndarray, int]:
    """Host-side decode: (events, n_dropped). ``events`` is a structured
    numpy array (EVENT_DTYPE) ordered oldest -> newest; ``n_dropped`` is how
    many older events were overwritten by wraparound."""
    data = np.asarray(ring.data)
    C = data.shape[0]
    head = int(ring.head)
    n = min(head, C)
    out = np.empty(n, EVENT_DTYPE)
    if n == 0:
        return out, 0
    # oldest surviving event sits at head % C when the ring has wrapped
    start = head % C if head > C else 0
    order = (start + np.arange(n)) % C
    out["tick"] = data[order, COL_TICK]
    out["tenant"] = data[order, COL_TENANT]
    out["page"] = data[order, COL_PAGE]
    out["direction"] = data[order, COL_DIR]
    out["hotness"] = data[order, COL_HOT].view(np.float32)
    return out, max(head - C, 0)


def ring_summary(ring: MigrationRing) -> dict:
    """Wraparound accounting for a ring (scalar head) or a fleet-batched
    ring (head [...]): how many events were ever recorded, how many the
    fixed capacity retains, and how many wrap dropped. Exported as the
    ``ring_events_total`` / ``ring_dropped_total`` Prometheus counters so
    operators can tell a quiet host from a ring that silently wrapped."""
    C = ring.data.shape[-2]
    head = np.asarray(ring.head, np.int64)
    return {
        "capacity": C,
        "recorded": head if head.ndim else int(head),
        "retained": np.minimum(head, C) if head.ndim else int(min(int(head), C)),
        "dropped": np.maximum(head - C, 0) if head.ndim else int(max(int(head) - C, 0)),
    }
