"""Prefill/decode disaggregation with live KV migration (DESIGN.md §12):
role-aware replicas, handoff_out/handoff_in, the disagg router's
transfer-vs-margin pricing with TTFT fallback, autoscaler role flips,
and byte-identity of migrated token streams on the real jax backend."""

import os
import sys

if "jax" not in sys.modules and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import numpy as np                                            # noqa: E402
import pytest                                                 # noqa: E402

from hypothesis import given, settings, strategies as st     # noqa: E402

from repro.cluster.autoscaler import (Autoscaler,             # noqa: E402
                                      AutoscalerConfig)
from repro.cluster.router import DisaggRouter, ROUTERS        # noqa: E402
from repro.core.baselines import make_scheduler               # noqa: E402
from repro.serving.engine import (EngineConfig, ServeEngine,  # noqa: E402
                                  SimBackend)
from repro.serving.kvcache import BlockManager                # noqa: E402
from repro.serving.request import (Request, ReqState,         # noqa: E402
                                   SLOSpec)
from repro.serving.run import (BackendSpec, ClusterSpec,      # noqa: E402
                               ExperimentSpec, run, run_cluster)
from repro.serving.workload import WorkloadSpec               # noqa: E402

CONTENDED = dict(rate=20.0, duration=8.0, seed=5, mix=(3, 2, 0),
                 slo_scale=0.25, system_prompt_len=1465,
                 shared_system_frac=1.0)

JAX_SPEC = dict(rate=1.5, duration=6.0, seed=0, mix=(2, 1, 1),
                prompt_cap=40, output_cap=12, slo_scale=20.0)
JAX_KW = dict(num_blocks=64, page=16, max_len=128, seed=0)
JAX_CFG = dict(max_batch=8, prefill_budget=32)


def _mk_req(rid=1, prompt=32, out=8, kind="latency", ttft=2.0,
            dag_id=None):
    slo = SLOSpec(kind, ttft=ttft, tbt=0.1, ttlt=60.0)
    return Request(rid=rid, app="chatbot", arrival=0.0, prompt_len=prompt,
                   true_output_len=out, slo=slo, dag_id=dag_id)


# ---------------------------------------------------------------------------
# Engine-level handoff protocol
# ---------------------------------------------------------------------------
def _src_engine(reqs, **cfg_kw):
    eng = ServeEngine(SimBackend.for_model(),
                      make_scheduler("tempo", use_predictor=False),
                      EngineConfig(role="prefill", **cfg_kw))
    eng.load(reqs, [])
    return eng


def test_handoff_roundtrip_completes_on_destination():
    """A prefill-complete request extracted with handoff_out and landed
    with handoff_in finishes on the destination with full output, and
    neither replica double-counts it."""
    src = _src_engine([_mk_req(rid=1, prompt=32, out=8)])
    src.step_once()                       # prefill (budget 2048 ≫ 32)
    r = src.requests.get(1)
    assert r is not None and r.prefill_remaining == 0
    out = src.handoff_out(1)
    assert out is not None
    req, pkg = out
    assert pkg["tokens"] >= 32 and pkg["n_pages"] >= 1 and pkg["bytes"] > 0
    assert 1 not in src.requests and 1 not in src.kv.seqs
    assert src.migrated_out == 1 and src.submitted_count == 0

    dst = ServeEngine(SimBackend.for_model(),
                      make_scheduler("tempo", use_predictor=False),
                      EngineConfig(role="decode"))
    dst.load([], [])
    dst.enqueue_handoff(req, pkg, t=0.5)
    assert dst.submitted_count == 1       # inbound counts in denominator
    fin = dst.run()
    assert [r.rid for r in fin] == [1]
    assert fin[0].decoded == 8 and fin[0].meta.get("migrated")
    assert dst.migrated_in == 1
    # destination claimed no prefill/prefix credit for remote compute
    assert dst.prefill_computed == 0 and dst.cached_tokens == 0


def test_handoff_out_guards_reject_unmigratable_states():
    """Mid-prefill, DAG-stage, finished, and unknown requests are never
    extracted."""
    src = _src_engine([_mk_req(rid=1, prompt=4096, out=8),
                       _mk_req(rid=2, prompt=32, out=8, dag_id=7)],
                      prefill_budget=64)
    src.step_once()
    assert src.requests[1].prefill_remaining > 0
    assert src.handoff_out(1) is None     # mid-prefill
    assert src.handoff_out(99) is None    # unknown rid
    r2 = src.requests.get(2)
    if r2 is not None:
        assert src.handoff_out(2) is None  # DAG stages never migrate
    assert src.migrated_out == 0


def test_handoff_in_under_pool_pressure_parks_swapped():
    """When the destination pool can't host the migrated pages even after
    eviction, the request parks host-side as swapped and still completes
    through the ordinary swap-in path."""
    src = _src_engine([_mk_req(rid=1, prompt=256, out=6)])
    src.step_once()
    req, pkg = src.handoff_out(1)

    dst = ServeEngine(SimBackend.for_model(),
                      make_scheduler("tempo", use_predictor=False),
                      EngineConfig(role="decode", kv_blocks=4))
    dst.load([], [])                      # 4×128 pool < 256-token payload?
    # 256 tokens need 2 pages of 128 — shrink further by occupying pool
    assert dst.kv.ensure(77, 512)         # 4 pages: pool now full
    dst.requests[77] = _mk_req(rid=77, prompt=512, out=4)
    dst.requests[77].state = ReqState.RUNNING   # not evictable
    dst.handoff_in(req, pkg)
    a = dst.kv.seqs[1]
    assert a.swapped and not a.blocks     # parked host-side
    dst.kv.check_invariants()
    # free the pool: the parked request must swap in and finish
    dst.requests.pop(77)
    dst.kv.release(77)
    fin = dst.run()
    assert any(r.rid == 1 and r.decoded == 6 for r in fin)


def test_handoff_out_donates_prompt_pages_to_prefix_cache():
    """The source publishes the migrated prompt into its prefix index, so
    followers with the same prompt still hit the prefill it paid for."""
    r = _mk_req(rid=1, prompt=256, out=8)
    toks = np.arange(256, dtype=np.int64) % 251
    r.meta["prompt_tokens"] = toks
    src = _src_engine([r])
    src.step_once()
    assert src.handoff_out(1) is not None
    blocks, cached = src.kv.match(toks, max_tokens=255)
    assert cached > 0                     # donated pages are matchable


# ---------------------------------------------------------------------------
# BlockManager adopt/park property test
# ---------------------------------------------------------------------------
@settings(max_examples=60, deadline=None)
@given(ops=st.lists(st.integers(0, 2 ** 20 - 1), min_size=1, max_size=100))
def test_blockmanager_adopt_park_invariants(ops):
    """Random interleavings of migrated-in adoption, host-side parking,
    growth, swap, and release never break pool invariants, and adopt
    never claims prefix-cache credit."""
    km = BlockManager(10, block_tokens=4)
    next_rid, live = 1, []
    for op in ops:
        kind = op % 5
        arg = op // 5
        if kind == 0:                     # migrate in: adopt fresh pages
            rid, next_rid = next_rid, next_rid + 1
            tokens = arg % 29 + 1
            n_pages = -(-tokens // 4) + arg % 2      # exact or +1 slack
            if km.adopt(rid, n_pages, tokens):
                assert km.seqs[rid].cached_tokens == 0
                live.append(rid)
        elif kind == 1:                   # migrate in under pressure: park
            rid, next_rid = next_rid, next_rid + 1
            km.park_swapped(rid, arg % 29 + 1)
            assert km.seqs[rid].swapped
            live.append(rid)
        elif live:
            rid = live[arg % len(live)]
            a = km.seqs[rid]
            if kind == 2:                 # decode growth
                if not a.swapped:
                    km.ensure(rid, a.tokens + arg % 5)
            elif kind == 3:               # swap round-trip
                km.swap_out(rid)
                km.swap_in(rid)
            else:                         # finish/shed
                km.release(rid)
                live.remove(rid)
        km.check_invariants()
        assert km.used_blocks + len(km.free) + km.reclaimable_blocks \
            == km.num_blocks


# ---------------------------------------------------------------------------
# Router and autoscaler units
# ---------------------------------------------------------------------------
class _FakeReplica:
    def __init__(self, rid, role, sched=None):
        self.rid = rid
        self.engine = ServeEngine(
            SimBackend.for_model(),
            sched or make_scheduler("tempo", use_predictor=False),
            EngineConfig(role=role))
        self.engine.load([], [])


def test_disagg_router_prices_transfer_and_ttft_fallback():
    rt = ROUTERS["disagg"]()
    assert isinstance(rt, DisaggRouter)
    src = _FakeReplica(0, "prefill")
    dst = _FakeReplica(1, "decode")
    req = _mk_req(rid=5, kind="latency", ttft=1.0)
    # cheap transfer: migrate to the decode replica
    assert rt.choose_decode_target(req, src, [src, dst], 0.0,
                                   t_xfer=0.001) is dst
    # transfer alone blows the TTFT budget while local decode would not:
    # decode locally (None)
    assert rt.choose_decode_target(req, src, [src, dst], 0.0,
                                   t_xfer=10.0) is None
    # throughput requests have no TTFT cliff — still migrate
    tr = _mk_req(rid=6, kind="throughput")
    assert rt.choose_decode_target(tr, src, [src, dst], 0.0,
                                   t_xfer=10.0) is dst
    # no non-prefill destination: stay local
    assert rt.choose_decode_target(req, src, [src], 0.0, 0.001) is None


def test_disagg_router_routes_singles_to_prefill_dags_to_decode():
    rt = ROUTERS["disagg"]()
    src = _FakeReplica(0, "prefill")
    dst = _FakeReplica(1, "decode")
    single = _mk_req(rid=1)
    assert rt.route("r", single, [src, dst], now=0.0) is src
    from repro.serving.request import CollectiveDag
    dag = CollectiveDag(dag_id=1, app="agent", arrival=0.0, ttlt=60.0,
                        stage_sizes=[1, 1])
    stage0 = [_mk_req(rid=2, dag_id=1)]
    assert rt.route("dag", (dag, stage0), [src, dst], now=0.0) is dst


def test_autoscaler_decide_role_streak_and_cooldown():
    ac = AutoscalerConfig(role_ratio=2.0, role_streak=3, role_floor=0.5,
                          cooldown=10.0)
    sc = Autoscaler(ac)
    # balanced load never flips
    assert sc.decide_role(0.0, 0.6, 0.6, n_mixed=2) is None
    # sustained prefill starvation: fires only on the 3rd consecutive obs
    assert sc.decide_role(1.0, 2.0, 0.1, n_mixed=2) is None
    assert sc.decide_role(2.0, 2.0, 0.1, n_mixed=2) is None
    assert sc.decide_role(3.0, 2.0, 0.1, n_mixed=2) == "prefill"
    assert sc.actions[-1][1] == "role->prefill"
    # cooldown gates the next flip even under sustained imbalance
    for t in (4.0, 5.0, 6.0):
        assert sc.decide_role(t, 0.1, 2.0, n_mixed=1) is None
    # direction change resets the streak
    sc2 = Autoscaler(ac)
    assert sc2.decide_role(0.0, 2.0, 0.1, n_mixed=1) is None
    assert sc2.decide_role(1.0, 0.1, 2.0, n_mixed=1) is None
    assert sc2.decide_role(2.0, 0.1, 2.0, n_mixed=1) is None
    assert sc2.decide_role(3.0, 0.1, 2.0, n_mixed=1) == "decode"
    # no mixed replica to flip
    sc3 = Autoscaler(ac)
    for t in (0.0, 1.0, 2.0, 3.0):
        assert sc3.decide_role(t, 2.0, 0.1, n_mixed=0) is None


# ---------------------------------------------------------------------------
# Cluster integration (sim)
# ---------------------------------------------------------------------------
def test_disagg_cluster_conserves_requests_and_beats_colocated():
    """The frozen contended arm: migration loses no requests fleet-wide,
    migrated counts match, and disaggregation beats colocated goodput."""
    spec = WorkloadSpec(**CONTENDED)
    co = run_cluster(ExperimentSpec(
        scheduler="vllm", workload=spec, warmup=64,
        cluster=ClusterSpec(router="slo-margin", n_replicas=2)))
    di = run_cluster(ExperimentSpec(
        scheduler="vllm", workload=spec, warmup=64,
        cluster=ClusterSpec(router="disagg", n_replicas=2,
                            roles=["prefill", "decode"])))
    assert di.fleet.migrated_in == di.fleet.migrated_out > 0
    # conservation: both arms account for the same submitted population
    assert di.fleet.n_admitted == co.fleet.n_admitted
    assert di.fleet.n_finished + di.fleet.n_shed \
        + di.fleet.n_unfinished == di.fleet.n_admitted
    assert di.goodput_frac > co.goodput_frac


def test_roles_thread_through_cluster_runner():
    spec = WorkloadSpec(rate=4.0, duration=3.0, seed=2, mix=(1, 1, 0))
    f = run_cluster(ExperimentSpec(
        scheduler="tempo", workload=spec, warmup=64,
        cluster=ClusterSpec(router="disagg",
                            roles=["prefill", "decode"])))
    assert f.n_replicas_peak == 2
    # per-replica migration accounting surfaces in the fleet summary
    assert f.fleet.migrated_in == sum(
        s.migrated_in for s in f.per_replica.values())
    assert f.fleet.migrated_out == sum(
        s.migrated_out for s in f.per_replica.values())


def test_other_routers_treat_roles_as_inert_metadata():
    """Roles without the disagg router must not migrate or crash."""
    spec = WorkloadSpec(rate=4.0, duration=3.0, seed=2)
    f = run_cluster(ExperimentSpec(
        scheduler="tempo", workload=spec, warmup=64,
        cluster=ClusterSpec(router="round-robin",
                            roles=["prefill", "decode"])))
    assert f.fleet.migrated_in == 0 and f.fleet.migrated_out == 0
    assert f.fleet.n_finished > 0


# ---------------------------------------------------------------------------
# Byte-identity on the real backend
# ---------------------------------------------------------------------------
def _merged_streams(sink):
    return sorted((rid, tuple(int(t) for t in toks))
                  for bk in sink for rid, toks in bk.generated.items())


def _jax_reference(tp=1):
    from repro.serving.run import make_backend
    kw = dict(JAX_KW, tp=tp) if tp > 1 else dict(JAX_KW)
    bk = make_backend("jax", kw)
    run(ExperimentSpec(scheduler="tempo", workload=WorkloadSpec(**JAX_SPEC),
                       engine=EngineConfig(tp=tp, **JAX_CFG),
                       backend=BackendSpec(kind=bk), warmup=64))
    return _merged_streams([bk])


def _jax_disagg(tp=1):
    sink = []
    f = run_cluster(ExperimentSpec(
        scheduler="tempo", workload=WorkloadSpec(**JAX_SPEC),
        engine=EngineConfig(tp=tp, **JAX_CFG),
        backend=BackendSpec(kind="jax", kwargs=dict(JAX_KW), sink=sink),
        warmup=64,
        cluster=ClusterSpec(router="disagg",
                            roles=["prefill", "decode"])))
    return _merged_streams(sink), f


def test_jax_migrated_streams_byte_identical():
    """The acceptance criterion: a disaggregated 1 prefill + 1 decode jax
    fleet with real migrations produces byte-identical token streams to a
    single colocated engine serving the same workload."""
    ref = _jax_reference()
    got, f = _jax_disagg()
    assert f.fleet.migrated_in > 0        # migrations actually happened
    assert got == ref


@pytest.mark.skipif("jax" in sys.modules and
                    len(__import__("jax").devices()) < 4,
                    reason="needs >= 4 devices (2 replicas x tp=2)")
def test_jax_migrated_streams_byte_identical_tp2():
    ref = _jax_reference(tp=2)
    got, f = _jax_disagg(tp=2)
    assert f.fleet.migrated_in > 0
    assert got == ref
