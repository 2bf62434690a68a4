"""Ahead-of-time compiles of the serving path for a described TPU v5e.

The TPU compiler is installed with JAX, so the serving kernels and a
full-width decode/prefill step can be compiled for a v5e chip that is
described, not attached.  That catches what interpret mode cannot: block
shapes the Mosaic lowering refuses, VMEM overruns, and kernels whose
lowering path raises before it ever reaches the compiler.  Shapes are
tinyllama-1.1b's published widths in bf16 with the backend's default
16-token pages; the fused decode kernel also compiles at the benchmark
cell's Yi-34B attention widths, where it DMAs the pool itself.

The topology is described inside a module fixture only: loading the TPU
library takes a process-wide lock, so it must happen in the one worker
that runs this file and never at import or collection time.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.kernels.paged_attention import (fused_decode_attention,
                                           fused_verify_attention,
                                           paged_attention)
from repro.models.model import build_model

B, W, PAGE, MAX_LEN, POOL = 8, 4, 16, 2048, 2048 + 1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    # compiles for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR",
                                                    "disabled"))
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:     # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def cfg():
    return get_config("tinyllama-1.1b")


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(sharding, cfg, rows=None, *, page=PAGE, max_len=MAX_LEN,
                 pool=POOL):
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    lead = (B,) if rows is None else (B, rows)
    bf, i32 = jnp.bfloat16, jnp.int32
    return dict(
        q=_sds(sharding, lead + (H, D), bf),
        kv_new=_sds(sharding, lead + (KV, D), bf),
        pages=_sds(sharding, (pool, page, KV, D), bf),
        tables=_sds(sharding, (B, max_len // page), i32),
        vec=_sds(sharding, (B,), i32))


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_fused_decode_kernel_compiles_for_v5e(one_chip, cfg):
    a = _kernel_args(one_chip, cfg)
    txt = _compiled_text(
        functools.partial(fused_decode_attention, interpret=False),
        a["q"], a["kv_new"], a["kv_new"], a["pages"], a["pages"],
        a["tables"], a["vec"])
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("page", [16, 128])
def test_fused_decode_kernel_compiles_for_v5e_at_yi34b_widths(one_chip,
                                                              page):
    """The benchmark cell's attention shape (H 56, KV 8 so G = 7, D 128,
    bf16, 4096-token tables: n_max 256 at 16-token pages), where the
    kernel DMAs live pages from the HBM pool itself: 8-page blocks at
    16-token pages, one page a block at 128."""
    a = _kernel_args(one_chip, get_config("yi-34b"), page=page,
                     max_len=4096, pool=36864 // page + 1)
    txt = _compiled_text(
        functools.partial(fused_decode_attention, interpret=False),
        a["q"], a["kv_new"], a["kv_new"], a["pages"], a["pages"],
        a["tables"], a["vec"])
    assert "tpu_custom_call" in txt


def test_paged_attention_kernel_compiles_for_v5e(one_chip, cfg):
    a = _kernel_args(one_chip, cfg)
    txt = _compiled_text(
        functools.partial(paged_attention, interpret=False),
        a["q"], a["pages"], a["pages"], a["tables"], a["vec"])
    assert "tpu_custom_call" in txt


def test_verify_kernel_compiles_for_v5e(one_chip, cfg):
    """The chip lowering of verification (``_verify_multirow``)."""
    a = _kernel_args(one_chip, cfg, rows=W)
    txt = _compiled_text(
        functools.partial(fused_verify_attention, interpret=False),
        a["q"], a["kv_new"], a["kv_new"], a["pages"], a["pages"],
        a["tables"], a["vec"], a["vec"])
    assert "tpu_custom_call" in txt


def _model_shapes(sharding, model, n_pages):
    put = lambda s: _sds(sharding, s.shape, s.dtype)
    params = jax.tree.map(put, jax.eval_shape(model.init,
                                              jax.random.PRNGKey(0)))
    pages = jax.tree.map(put, model.paged_cache_specs(n_pages, PAGE))
    return params, pages


def test_full_width_decode_step_compiles_for_v5e(one_chip, cfg):
    model = build_model(cfg)
    params, pages = _model_shapes(one_chip, model, POOL)
    i32 = jnp.int32
    step = functools.partial(model.decode_paged, interpret=False,
                             fused=True)
    compiled = jax.jit(step).lower(
        params, pages, _sds(one_chip, (B, 1), i32),
        _sds(one_chip, (B,), i32),
        _sds(one_chip, (B, MAX_LEN // PAGE), i32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    logits, new_pages = compiled.out_info
    assert logits.shape == (B, cfg.vocab_size)
    assert all(p.dtype == jnp.bfloat16 for p in jax.tree.leaves(new_pages))


def test_full_width_prefill_chunk_compiles_for_v5e(one_chip, cfg):
    model = build_model(cfg)
    params, pages = _model_shapes(one_chip, model, POOL)
    i32 = jnp.int32
    compiled = jax.jit(model.prefill_paged).lower(
        params, pages, _sds(one_chip, (1, 256), i32),
        _sds(one_chip, (), i32),
        _sds(one_chip, (MAX_LEN // PAGE,), i32),
        _sds(one_chip, (), i32)).compile()
    assert all(p.dtype == jnp.bfloat16
               for p in jax.tree.leaves(compiled.out_info))
