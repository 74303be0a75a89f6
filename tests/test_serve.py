"""Serving-path tests: tiered paged decode must equal the full-sequence
forward bit-for-bit(ish, f32) even while Equilibria migrates pages."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import TieringConfig
from repro.models.params import init_params
from repro.models.transformer import encode_frames, model_forward, model_specs
from repro.serve.decode import (build_serve_step, compute_cross_kv,
                                init_serve_state)

from conftest import arch_params

KEY = jax.random.PRNGKey(0)
TCFG = TieringConfig(n_tenants=2, page_tokens=4, thrash_table_slots=64,
                     lower_protection=(2, 2), upper_bound=(3, 3))


def _decode_all(cfg, params, state, toks, tcfg=TCFG):
    step = jax.jit(build_serve_step(cfg, tcfg, toks.shape[0], toks.shape[1]))
    outs = []
    for i in range(toks.shape[1]):
        logits, state = step(params, state, toks[:, i:i + 1])
        outs.append(logits[:, 0])
    return jnp.stack(outs, axis=1), state


@pytest.mark.parametrize("arch", arch_params())
def test_decode_matches_forward_with_migrations(arch):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32",
                              param_dtype="float32")
    if cfg.moe is not None:
        # exact decode/forward equivalence needs drop-free capacity
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    params = init_params(KEY, model_specs(cfg))
    B, steps = 2, 24
    batch = {"tokens": jax.random.randint(KEY, (B, steps), 0, cfg.vocab_size)}
    state = init_serve_state(cfg, TCFG, B, steps)
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            jax.random.PRNGKey(7), (B, cfg.num_image_tokens, cfg.d_model)) * 0.1
        ck, cv = compute_cross_kv(params, cfg, batch["image_embeds"])
        state["cross_k"], state["cross_v"] = ck, cv
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(7), (B, cfg.encoder_seq, cfg.d_model)) * 0.1
        enc = encode_frames(params, batch["frames"], cfg, remat="none")
        ck, cv = compute_cross_kv(params, cfg, enc)
        state["cross_k"], state["cross_v"] = ck, cv

    serve_logits, state = _decode_all(cfg, params, state, batch["tokens"])
    ref_logits, _ = model_forward(params, batch, cfg, remat="none")
    err = float(jnp.abs(serve_logits - ref_logits).max())
    assert err < 1e-3, err
    if "kv" in state:
        kv = state["kv"]
        # tight bounds forced real tier activity
        assert int((kv.slow_page >= 0).sum()) > 0
        assert int(kv.seq_len[0]) == steps


def test_swa_ring_wrap_correct():
    cfg = dataclasses.replace(get_smoke_config("h2o_danube_3_4b"),
                              dtype="float32", param_dtype="float32")
    params = init_params(KEY, model_specs(cfg))
    B, steps = 2, 96    # window=32, page=4: ring wraps multiple times
    toks = jax.random.randint(KEY, (B, steps), 0, cfg.vocab_size)
    state = init_serve_state(cfg, TCFG, B, steps)
    serve_logits, _ = _decode_all(cfg, params, state, toks)
    ref_logits, _ = model_forward(params, {"tokens": toks}, cfg, remat="none")
    assert float(jnp.abs(serve_logits - ref_logits).max()) < 1e-3


def test_fairness_counters_on_serving_path():
    """Equilibria inside serve_step: counters move, protections respected."""
    cfg = dataclasses.replace(get_smoke_config("llama32_1b"), dtype="float32")
    params = init_params(KEY, model_specs(cfg))
    B, steps = 8, 40
    tcfg = TieringConfig(n_tenants=2, page_tokens=4, thrash_table_slots=64,
                         lower_protection=(12, 12), upper_bound=(0, 0))
    toks = jax.random.randint(KEY, (B, steps), 0, cfg.vocab_size)
    state = init_serve_state(cfg, tcfg, B, steps)
    _, state = _decode_all(cfg, params, state, toks, tcfg=tcfg)
    kv = state["kv"]
    assert int(kv.counters.allocations.sum()) == B * (steps // 4)
    assert int(kv.t) == steps


def test_unrolled_inplace_decode_matches_scan_path():
    """The unrolled (in-place pool update) decode path used by the dry-run
    must equal the scan path bit-for-bit."""
    from repro.models.unroll import set_unroll
    cfg = dataclasses.replace(get_smoke_config("qwen3_32b"), dtype="float32",
                              param_dtype="float32")
    params = init_params(KEY, model_specs(cfg))
    B, steps = 2, 16
    toks = jax.random.randint(KEY, (B, steps), 0, cfg.vocab_size)
    outs = {}
    for unroll in (False, True):
        set_unroll(unroll)
        try:
            state = init_serve_state(cfg, TCFG, B, steps)
            step = jax.jit(build_serve_step(cfg, TCFG, B, steps))
            got = []
            for i in range(steps):
                logits, state = step(params, state, toks[:, i:i + 1])
                got.append(logits[:, 0])
            outs[unroll] = jnp.stack(got, axis=1)
        finally:
            set_unroll(False)
    assert float(jnp.abs(outs[True] - outs[False]).max()) < 1e-5


def test_tpp_mode_on_serving_path():
    cfg = dataclasses.replace(get_smoke_config("llama32_1b"), dtype="float32")
    params = init_params(KEY, model_specs(cfg))
    B, steps = 4, 16
    toks = jax.random.randint(KEY, (B, steps), 0, cfg.vocab_size)
    state = init_serve_state(cfg, TCFG, B, steps)
    step = jax.jit(build_serve_step(cfg, TCFG, B, steps, mode="tpp"))
    for i in range(steps):
        logits, state = step(params, state, toks[:, i:i + 1])
    ref, _ = model_forward(params, {"tokens": toks}, cfg, remat="none")
    assert float(jnp.abs(logits[:, 0] - ref[:, -1]).max()) < 1e-3


def test_compile_cache_location(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache sits at
    a fixed <checkout>/.jax_cache, never at a per-run path."""
    import pathlib

    from repro.launch.compile_cache import enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        checkout = pathlib.Path(__file__).resolve().parents[1]
        assert path == str(checkout / ".jax_cache") == enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
