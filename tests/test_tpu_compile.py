"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

The selection and page-move kernels, and the whole tick with and without
them, are compiled at the scale point (T=64 tenants, L=262144 pages,
k=256) by the TPU compiler that ships with jaxlib. Interpret mode cannot
see what only Mosaic refuses (tiling, VMEM, unsupported primitives); these
compiles do, at no chip time. Nothing runs, so nothing here is a result
or a time.

The topology is described inside a module-scoped fixture, never at import:
only the process that runs these tests loads the TPU compiler.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

T, L, K = 64, 262144, 256
HBM_BYTES = 16 * 1024 ** 3          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except RuntimeError as e:  # no TPU compiler in this jaxlib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, shapes, sharding):
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)
    compiled = jax.jit(fn).lower(*args).compile()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes
            - m.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled.as_text()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _kernel_case(name):
    from repro.kernels.migrate import ops as KMIG
    from repro.kernels.select import ops as KSEL
    if name == "seg_topk_static":
        return (lambda s, v, q: KSEL.seg_topk(s, v, q, K, impl="pallas"),
                (_sds((T, 4096), jnp.float32), _sds((T, 4096), jnp.bool_),
                 _sds((T,), jnp.int32)))
    if name == "seg_topk_dynamic":   # the dynamic strategy's S = L rows
        return (lambda s, v, q: KSEL.seg_topk(s, v, q, K, impl="pallas"),
                (_sds((T, L), jnp.float32), _sds((T, L), jnp.bool_),
                 _sds((T,), jnp.int32)))
    if name == "seg_reduce":
        return (lambda x, v: KSEL.seg_reduce(x, v, impl="pallas"),
                (_sds((T, 4096), jnp.int32), _sds((T, 4096), jnp.bool_)))
    if name == "seg_sums":
        return (lambda x, v: KSEL.seg_sums(x, v, impl="pallas"),
                (_sds((T, 4096), jnp.int32), _sds((T, 4096), jnp.bool_)))
    assert name == "commit_moves"
    from repro.configs.base import TieringConfig
    N, C = T * K, TieringConfig().obs_ring_capacity

    def moves(tier, ring, head, pages, take, ten, hot, t):
        return KMIG.commit_moves(tier, ring, head, pages, take, ten, hot, t,
                                 direction=1, to_tier=1, impl="pallas")
    return moves, (_sds((L,), jnp.int32), _sds((C, 5), jnp.int32),
                   _sds((), jnp.int32), _sds((N,), jnp.int32),
                   _sds((N,), jnp.bool_), _sds((N,), jnp.int32),
                   _sds((N,), jnp.float32), _sds((), jnp.int32))


@pytest.mark.parametrize("name", ["seg_topk_static", "seg_topk_dynamic",
                                  "seg_reduce", "seg_sums", "commit_moves"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_case(name)
    assert "tpu_custom_call" in _compile(fn, shapes, one_chip)


@pytest.fixture(scope="module")
def tick_hlo(one_chip):
    """Compiled HLO text of one equilibria tick at the scale point
    (benchmarks/scale_sweep), by impl; each impl compiles once."""
    from benchmarks.scale_sweep import scale_point
    from repro.core.engine import make_tick
    from repro.core.state import init_state
    cfg, owner = scale_point(T, L)
    texts = {}

    def get(impl):
        if impl not in texts:
            tick = make_tick(cfg, owner, "equilibria", k_max=K, impl=impl)
            state = jax.eval_shape(lambda: init_state(cfg, L, owner=owner))
            inputs = (_sds((L,), jnp.float32), _sds((L,), jnp.bool_))
            texts[impl] = _compile(tick, (state, inputs), one_chip)
        return texts[impl]
    return get


@pytest.mark.parametrize("impl", ["batched", "pallas"])
def test_tick_compiles_for_v5e(tick_hlo, impl):
    """One whole equilibria tick at the scale point."""
    assert ("tpu_custom_call" in tick_hlo(impl)) == (impl == "pallas")


_HLO_DEF = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]")
_HLO_GATHER = re.compile(r" gather\(%([^,)\s]+).*slice_sizes=\{([\d,]*)\}")


def _elements(dims: str) -> int:
    return int(np.prod([int(d) for d in dims.split(",") if d]))


def test_static_tick_builds_rows_without_element_gather(tick_hlo):
    """The static tick's padded [T, S] tenant rows are window copies: no
    gather is left that reads an [L] page vector one element at a time
    into T*S lanes (the per-element row gather runs at a few cycles per
    element on the TPU)."""
    text = tick_hlo("batched")
    shapes = {m.group(1): m.group(2) for m in map(_HLO_DEF.match,
                                                  text.splitlines()) if m}
    S = L // T                      # the scale point's equal tenants
    element_gathers = []
    for line in text.splitlines():
        g, d = _HLO_GATHER.search(line), _HLO_DEF.match(line)
        if g and d and (_elements(shapes[g.group(1)]), _elements(d.group(2)),
                        g.group(2)) == (L, T * S, "1"):
            element_gathers.append(line.strip()[:200])
    assert not element_gathers, element_gathers


_HLO_TYPED = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]")
_HLO_INDEXED = re.compile(r" (scatter|gather)\(([^)]*)\)")


@pytest.fixture(scope="module")
def churn_tick_hlo(one_chip):
    """Compiled HLO text of one dynamic-ownership (churn) tick: T=64 slots
    over a pool of L pages, rates of L/T lanes a slot."""
    from benchmarks.scale_sweep import scale_point
    from repro.core.churn import make_churn_tick
    from repro.core.state import init_state
    cfg, _ = scale_point(T, L)
    tick = make_churn_tick(cfg, L, "equilibria", k_max=K)
    state = jax.eval_shape(lambda: init_state(cfg, L))
    inputs = (_sds((T, L // T), jnp.float32), _sds((T,), jnp.int32))
    return _compile(tick, (state, inputs), one_chip)


def _page_axis_ops(text):
    """(kind, output (dtype, elements), operands [(dtype, elements)]) of
    every scatter and gather that indexes the page axis (L indices)."""
    shapes = {m.group(1): (m.group(2), _elements(m.group(3)))
              for m in map(_HLO_TYPED.match, text.splitlines()) if m}
    found = []
    for line in text.splitlines():
        op, d = _HLO_INDEXED.search(line), _HLO_TYPED.match(line)
        if not (op and d):
            continue
        args = [shapes.get(a.strip().lstrip("%"), ("?", 0))
                for a in op.group(2).split(",")]
        if args[1][1] % L == 0:               # the indices: L lanes (x k)
            found.append((op.group(1), (d.group(2), _elements(d.group(3))),
                          args))
    return found


def test_churn_tick_has_no_per_tenant_scatter_or_gather(churn_tick_hlo):
    """On the TPU the churn tick's integer per-tenant sums and its lookups
    from (T+1)-entry tables are compare-and-reduce, not a scatter-add or a
    gather over the page axis (either runs near-serially over its L
    indices on the chip). The page-axis scatters and gathers left are
    pinned by count, so the census can only fall."""
    ops = _page_axis_ops(churn_tick_hlo)
    per_tenant = [
        o for o in ops
        if (o[0] == "scatter" and o[1][1] == T + 1 and o[2][2][0] != "f32")
        or (o[0] == "gather" and o[2][0][1] == T + 1)]
    assert not per_tenant, per_tenant
    # left: 2 f32 perf-model sums, 6 inverse permutations of the segment
    # ranks, 3 residency histograms + 2 thrash-table writes, 3 migration
    # ring appends; 4 thrash-table reads, the rates[owner, rank] gather
    kinds = [o[0] for o in ops]
    assert (kinds.count("scatter"), kinds.count("gather")) == (16, 5), ops
