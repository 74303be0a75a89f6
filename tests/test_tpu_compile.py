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
import jax
import jax.numpy as jnp
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


@pytest.mark.parametrize("impl", ["batched", "pallas"])
def test_tick_compiles_for_v5e(one_chip, impl):
    """One whole equilibria tick at the scale point (benchmarks/scale_sweep)."""
    from benchmarks.scale_sweep import scale_point
    from repro.core.engine import make_tick
    from repro.core.state import init_state
    cfg, owner = scale_point(T, L)
    tick = make_tick(cfg, owner, "equilibria", k_max=K, impl=impl)
    state = jax.eval_shape(lambda: init_state(cfg, L, owner=owner))
    inputs = (_sds((L,), jnp.float32), _sds((L,), jnp.bool_))
    text = _compile(tick, (state, inputs), one_chip)
    assert ("tpu_custom_call" in text) == (impl == "pallas")
