"""Tensor-parallel serving (DESIGN.md §8): sharded-vs-single-device
equivalence of the PagedJaxBackend.

Token streams under --tp N must be byte-identical to --tp 1: attention is
per-head (shard-local softmax), KV appends/gathers are shard-local, the
vocab all-gather is a pure concatenation, and the only cross-shard
reductions (wo / w_down psums) perturb logits at ulp level — far below
the sampling decision boundaries of a random-init reduced model.

Multi-device runs need >1 local device.  When this module is imported
before jax (e.g. ``pytest tests/test_tp.py``) it forces 8 host CPU
devices itself; under the full suite (jax already initialised
single-device) the device-bound tests skip — CI's ``smoke-sharded`` lane
runs them with XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

import os
import sys

if "jax" not in sys.modules and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import jax                                                    # noqa: E402
import numpy as np                                            # noqa: E402
import pytest                                                 # noqa: E402

from repro.configs.archs import reduced_config                # noqa: E402
from repro.core.baselines import make_scheduler               # noqa: E402
from repro.launch.sharding import (paged_page_specs,          # noqa: E402
                                   paged_param_specs, paged_tp_plan)
from repro.serving.engine import EngineConfig, ServeEngine    # noqa: E402
from repro.serving.jax_backend import PagedJaxBackend         # noqa: E402
from repro.serving.request import Request, SLOSpec            # noqa: E402

N_DEV = len(jax.devices())
need2 = pytest.mark.skipif(N_DEV < 2, reason="needs >=2 devices")
need4 = pytest.mark.skipif(N_DEV < 4, reason="needs >=4 devices")


# ---------------------------------------------------------------------------
# Plan / spec unit tests (no devices needed)
# ---------------------------------------------------------------------------
def test_paged_tp_plan_divisibility():
    cfg = reduced_config("tinyllama-1.1b")     # H=4, KV=2, d_ff=128, V=256
    assert paged_tp_plan(cfg, 1) == dict(tp=1, attn=False, mlp=False,
                                         vocab=False)
    p2 = paged_tp_plan(cfg, 2)
    assert p2["attn"] and p2["mlp"] and p2["vocab"]
    p4 = paged_tp_plan(cfg, 4)                 # KV=2 % 4 != 0 -> fallback
    assert not p4["attn"] and p4["mlp"] and p4["vocab"]


def test_paged_specs_divide_every_leaf():
    """Every 'model'-sharded dim must divide by tp; GQA groups must stay
    whole (H and KV shard together or not at all)."""
    from jax.sharding import PartitionSpec as P
    cfg = reduced_config("tinyllama-1.1b")
    from repro.models.model import build_model
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pages = model.paged_cache_specs(8, 16)
    is_p = lambda x: isinstance(x, P)
    for tp in (2, 4):
        specs = paged_param_specs(cfg, tp, params)
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(specs, is_leaf=is_p)
        plan = paged_tp_plan(cfg, tp)
        for leaf, spec in zip(flat_p, flat_s):
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is not None:
                    assert dim % tp == 0, (leaf.shape, tuple(spec), tp)
        gspecs = jax.tree.leaves(paged_page_specs(cfg, tp, pages),
                                 is_leaf=is_p)
        for leaf, spec in zip(jax.tree.leaves(pages), gspecs):
            kv_ax = tuple(spec)[leaf.ndim - 2]
            assert (kv_ax == "model") == plan["attn"]


# ---------------------------------------------------------------------------
# Engine-level stream equivalence
# ---------------------------------------------------------------------------
def _mk_reqs(n=2, prompt=30, out=10, kind="throughput"):
    return [Request(rid=i + 1, app="chatbot", arrival=0.0,
                    prompt_len=prompt, true_output_len=out,
                    slo=SLOSpec(kind, ttlt=1e6))
            for i in range(n)]


def _run(tp, num_blocks=4, temperature=0.0, top_k=0, n=2):
    """Tiny pool (4 per-device blocks) so prefill+decode cross page
    boundaries with the pool exhausted — at least one eviction/swap
    round-trips through host copies on the sharded pool too."""
    be = PagedJaxBackend(num_blocks=num_blocks, page=16, max_len=64,
                         seed=0, tp=tp, temperature=temperature,
                         top_k=top_k)
    eng = ServeEngine(be, make_scheduler("tempo", use_predictor=False),
                      EngineConfig(max_batch=2, prefill_budget=16, tp=tp))
    eng.load(_mk_reqs(n=n), [])
    fin = eng.run()
    assert len(fin) == n
    return eng, be, {r.rid: list(be.generated[r.rid]) for r in fin}


@need2
def test_tp2_streams_identical_greedy():
    _, be1, s1 = _run(tp=1)
    _, be2, s2 = _run(tp=2)
    assert be2.plan["attn"], "KV=2 must shard at tp=2"
    assert be2.num_blocks == 2 * be1.num_blocks  # mesh-wide aggregate pool
    assert s1 == s2


@need2
def test_tp2_streams_identical_seeded_temperature():
    _, _, s1 = _run(tp=1, temperature=0.8, top_k=20, n=3)
    _, _, s2 = _run(tp=2, temperature=0.8, top_k=20, n=3)
    assert s1 == s2


@need2
def test_tp2_multi_step_decode_streams_identical():
    """Multi-step dispatch under tensor parallelism: the lax.scan decode
    window runs INSIDE the shard_map, so n>1 must reproduce the tp=1
    single-step streams byte-for-byte (DESIGN.md §10)."""
    def run(tp, decode_steps):
        be = PagedJaxBackend(num_blocks=16, page=16, max_len=64, seed=0,
                             tp=tp)
        eng = ServeEngine(be, make_scheduler("tempo", use_predictor=False),
                          EngineConfig(max_batch=2, prefill_budget=16,
                                       tp=tp, decode_steps=decode_steps))
        eng.load(_mk_reqs(n=2), [])
        fin = eng.run()
        assert len(fin) == 2
        if decode_steps > 1:
            assert any(n > 1 for n in be._decode_n_cache), \
                "fast path never engaged"
        return {r.rid: list(be.generated[r.rid]) for r in fin}

    ref = run(tp=1, decode_steps=1)
    assert run(tp=2, decode_steps=4) == ref
    assert run(tp=1, decode_steps=4) == ref


@need2
def test_tp2_spec_streams_identical():
    """Speculative decoding under tensor parallelism: the verify forward
    runs inside the shard_map and accept/reject happens on replicated
    logits, so spec-on tp=2 streams must equal plain tp=1 byte-for-byte
    (DESIGN.md §11)."""
    def run(tp, depth):
        be = PagedJaxBackend(num_blocks=16, page=16, max_len=64, seed=0,
                             tp=tp)
        eng = ServeEngine(be, make_scheduler("tempo", use_predictor=False),
                          EngineConfig(max_batch=2, prefill_budget=16,
                                       tp=tp, spec_depth_max=depth))
        # prompt lengths whose greedy continuations repeat early enough
        # for the n-gram drafter to fire within 12 output tokens
        eng.load([Request(rid=i + 1, app="chatbot", arrival=0.0,
                          prompt_len=20 + 3 * i, true_output_len=12,
                          slo=SLOSpec("throughput", ttlt=1e6))
                  for i in range(2)], [])
        fin = eng.run()
        assert len(fin) == 2
        if depth:
            assert eng.spec_proposed > 0, "spec path never engaged"
        return {r.rid: list(be.generated[r.rid]) for r in fin}

    ref = run(tp=1, depth=0)
    assert run(tp=2, depth=4) == ref
    assert run(tp=1, depth=4) == ref


@need2
def test_tp2_swap_roundtrip_byte_exact():
    """Evictions on the SHARDED pool (tp=2, 2 per-device blocks -> 4
    aggregate) must restore KV byte-exactly: streams equal the
    no-eviction tp=1 big-pool truth."""
    eng, _, small = _run(tp=2, num_blocks=2)
    assert eng.swap_bytes > 0, "pool too large: no eviction exercised"
    _, _, big = _run(tp=1, num_blocks=32)
    assert small == big


@need4
def test_tp4_replicated_kv_fallback_streams_identical():
    """num_kv_heads=2 % tp=4 != 0: attention falls back to replication
    (pool unscaled) while MLP/vocab still shard — streams stay exact."""
    _, be4, s4 = _run(tp=4)
    assert not be4.plan["attn"] and be4.plan["mlp"]
    assert be4.num_blocks == 4      # no aggregate scaling when replicated
    _, _, s1 = _run(tp=1)
    assert s1 == s4


@need2
def test_tp2_prefix_cache_cow_byte_identical_on_vs_off():
    """Prefix-cache adoption + COW forks on a KV-head-sharded pool: the
    cache-on multiturn run must emit the cache-off streams exactly."""
    from repro.serving.workload import WorkloadGen, WorkloadSpec

    def run_mt(cache):
        spec = WorkloadSpec(scenario="multiturn", rate=0.5, duration=8.0,
                            seed=0, turns=(2, 3), think_time=40.0,
                            system_prompt_len=8, shared_system_frac=1.0,
                            prompt_cap=8, output_cap=4, slo_scale=50.0)
        gen = WorkloadGen(spec)
        be = PagedJaxBackend(num_blocks=32, page=16, max_len=128, seed=0,
                             tp=2)
        eng = ServeEngine(be, make_scheduler("sarathi"),
                          EngineConfig(max_batch=4, prefill_budget=32,
                                       prefix_cache=cache, tp=2),
                          workload=gen)
        singles, dags = gen.generate()
        eng.load(singles, dags)
        fin = eng.run()
        return eng, {r.rid: list(be.generated[r.rid]) for r in fin}

    eon, on = run_mt(True)
    eoff, off = run_mt(False)
    assert on == off
    assert eon.prefix_hits > 0 and eon.cow_forks > 0
    eon.kv.check_invariants()


@need2
def test_tp2_streams_identical_with_telemetry():
    """Telemetry must be observation-only on the sharded path too: a tp=2
    run with registry+tracer attached emits byte-identical streams (and
    records real backend profiling counters)."""
    from repro.obs import MetricsRegistry, Tracer

    def run_obs(telemetry):
        be = PagedJaxBackend(num_blocks=4, page=16, max_len=64, seed=0,
                             tp=2)
        extra = dict(obs=MetricsRegistry(), tracer=Tracer()) \
            if telemetry else {}
        eng = ServeEngine(be, make_scheduler("tempo", use_predictor=False),
                          EngineConfig(max_batch=2, prefill_budget=16,
                                       tp=2), **extra)
        eng.load(_mk_reqs(n=2), [])
        fin = eng.run()
        streams = {r.rid: list(be.generated[r.rid]) for r in fin}
        return streams, extra.get("obs")

    s_off, _ = run_obs(False)
    s_on, obs = run_obs(True)
    assert s_on == s_off
    assert obs.value_of("jax_compiles_total") > 0


@need2
def test_cluster_replicas_with_tp_meshes():
    """2 replicas × tp=2 meshes (distinct device slices): the fleet
    serves real sharded work and per-token texts match a tp=1 fleet."""
    from repro.cluster.engine import ClusterEngine
    from repro.cluster.router import make_router

    def run_fleet(tp):
        backends = {}
        devs = jax.devices()

        def factory(rid):
            sl = [devs[(rid * tp + i) % len(devs)] for i in range(tp)]
            backends[rid] = PagedJaxBackend(num_blocks=16, page=16,
                                            max_len=64, seed=0, tp=tp,
                                            devices=sl)
            return ServeEngine(backends[rid],
                               make_scheduler("tempo", use_predictor=False),
                               EngineConfig(max_batch=2, prefill_budget=16,
                                            tp=tp))

        cluster = ClusterEngine(factory, make_router("round-robin"),
                                n_replicas=2)
        reqs = _mk_reqs(n=4, prompt=20, out=6)
        for i, r in enumerate(reqs):
            r.arrival = 0.05 * i
        fin = cluster.run(iter([(r.arrival, "r", r) for r in reqs]))
        texts = {}
        for rid, rs in fin.items():
            for r in rs:
                texts[r.rid] = list(backends[rid].generated[r.rid])
        assert len(texts) == 4
        return texts

    assert run_fleet(2) == run_fleet(1)
