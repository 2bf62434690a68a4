"""Sharding policy: PartitionSpec validity (every named axis divides its
dim), mode behaviours, cache specs, AxisCtx prefix fallback."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.launch import sharding as sh
from repro.models.partition import AxisCtx, best_axes


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


MESH = FakeMesh({"data": 16, "model": 16})
MESH_MP = FakeMesh({"pod": 2, "data": 16, "model": 16})


def _axis_product(mesh, entry):
    if entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _valid(mesh, spec, shape):
    for dim, entry in zip(shape, tuple(spec) + (None,) * len(shape)):
        if dim % _axis_product(mesh, entry) != 0:
            return False
    return True


@settings(max_examples=60, deadline=None)
@given(dims=st.lists(st.sampled_from(
    [1, 2, 7, 8, 16, 24, 56, 128, 384, 2048, 7168, 20480, 73728]),
    min_size=1, max_size=4))
def test_generic_spec_always_divisible(dims):
    spec = sh._generic_spec(MESH, tuple(dims))
    assert _valid(MESH, spec, tuple(dims))


@pytest.mark.parametrize("arch", ["yi-34b", "kimi-k2-1t-a32b",
                                  "jamba-v0.1-52b", "minicpm3-4b"])
@pytest.mark.parametrize("mode", ["fsdp", "tp"])
def test_param_specs_valid_for_all_leaves(arch, mode):
    cfg = get_config(arch)
    from repro.models import build_model
    m = build_model(cfg)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))

    def check(path, leaf):
        spec = sh.param_pspec(cfg, MESH, path, leaf.shape, mode)
        assert _valid(MESH, spec, leaf.shape), (path, leaf.shape, spec)
        return leaf
    jax.tree_util.tree_map_with_path(check, shapes)


def test_expert_weights_pinned_for_ep():
    cfg = get_config("kimi-k2-1t-a32b")
    from jax.tree_util import DictKey
    path = (DictKey("units"), DictKey("l0"), DictKey("w_gate"))
    spec = sh.param_pspec(cfg, MESH, path,
                          (60, cfg.num_experts, cfg.d_model,
                           cfg.d_ff_expert))
    assert spec[1] == "model"          # expert dim on the EP axis
    assert spec[2] == "data"           # d_model storage-sharded


def test_best_axes_prefix_fallback():
    class M:
        shape = {"pod": 2, "data": 16, "model": 16}
    assert best_axes(M(), 512, ("pod", "data", "model")) == \
        ("pod", "data", "model")
    assert best_axes(M(), 256, ("pod", "data", "model")) is None or True
    # 256 % 512 != 0 -> falls back to ('pod','data') = 32
    assert best_axes(M(), 256, ("pod", "data", "model")) == ("pod", "data")
    assert best_axes(M(), 1, ("data",)) is None


def test_make_ctx_axes():
    cfg = get_config("yi-34b")
    ctx = sh.make_ctx(cfg, None, "train")
    assert ctx.batch == ("data",) and ctx.seq == ("model",)
    xcfg = get_config("xlstm-1.3b")
    # phase-aware recurrent policy (EXPERIMENTS.md §Perf iteration A):
    # training keeps the sequence local (sLSTM backward blows up on a
    # gathered sequence); prefill/decode sequence-shard the mLSTM.
    ctx_tr = sh.make_ctx(xcfg, None, "train")
    assert ctx_tr.seq == () and "model" in ctx_tr.batch
    ctx_pf = sh.make_ctx(xcfg, None, "prefill")
    assert ctx_pf.seq == ("model",)


def test_cache_pspec_decode_modes():
    cfg = get_config("yi-34b")
    ctx = AxisCtx(mesh=None, batch=("data",))

    class Ctx2(AxisCtx):
        pass
    from jax.tree_util import DictKey
    real = jax.make_mesh((1, 1), ("data", "model"))
    ctx = AxisCtx(mesh=real, batch=("data",), decode_tp=False)
    spec = sh.cache_pspec(ctx, (DictKey("units"), DictKey("l0"),
                                DictKey("k")), (15, 16, 32768, 8, 128))
    assert spec[2] == "model"          # sequence-sharded cache
    ctx_tp = AxisCtx(mesh=real, batch=("data",), decode_tp=True)
    spec2 = sh.cache_pspec(ctx_tp, (DictKey("units"), DictKey("l0"),
                                    DictKey("k")), (15, 16, 32768, 8, 128))
    assert spec2[4] == "model"         # head_dim-sharded cache (TP mode)
