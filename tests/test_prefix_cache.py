"""Shared-prefix KV reuse: refcounted COW BlockManager unit + property
tests, engine-level cache-on/off accounting on the multi-turn and agentic
workloads, reclaimable-aware KV pressure, and the prefix-affinity router."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.baselines import make_scheduler
from repro.serving.engine import EngineConfig, ServeEngine, SimBackend
from repro.serving.kvcache import BlockManager, page_hash_chain
from repro.serving.run import ExperimentSpec, run
from repro.serving.workload import WorkloadGen, WorkloadSpec

STREAM = (np.arange(4096) * 131 + 17) % 256     # shared token universe


# ---------------------------------------------------------------------------
# BlockManager unit tests
# ---------------------------------------------------------------------------
def test_match_adopt_roundtrip_full_pages_and_tail():
    km = BlockManager(16, block_tokens=4)
    assert km.ensure(1, 11)
    assert km.register(1, STREAM[:11]) > 0
    km.release(1)
    km.check_invariants()
    assert km.reclaimable_blocks == 3            # 2 full + 1 tail, all cold
    # follower extends the stream: 2 full pages + the 3-token tail
    blocks, cached = km.match(STREAM[:20], max_tokens=19)
    assert cached == 11 and len(blocks) == 3
    km.adopt(2, blocks, cached)
    km.check_invariants()
    assert km.reclaimable_blocks == 0            # resurrected out of LRU
    assert km.seqs[2].cached_tokens == 11


def test_match_caps_at_prompt_len_minus_one():
    km = BlockManager(8, block_tokens=4)
    assert km.ensure(1, 8)
    km.register(1, STREAM[:8])
    km.release(1)
    # identical 8-token prompt: both pages match but the claim is capped,
    # so the final token is always computed by the new request
    blocks, cached = km.match(STREAM[:8], max_tokens=7)
    assert cached == 7 and len(blocks) == 2


def test_cow_fork_preserves_registered_page():
    km = BlockManager(8, block_tokens=4)
    assert km.ensure(1, 6)
    km.register(1, STREAM[:6])
    km.release(1)
    blocks, cached = km.match(STREAM[:12], max_tokens=11)
    assert cached == 6
    km.adopt(2, blocks, cached)
    tail = km.seqs[2].blocks[1]
    old, new = km.fork_for_append(2, 6)          # append into the tail page
    assert old == tail and new != tail           # immutable: copy, not write
    km.check_invariants()
    # the original tail went back to the cold cache, still matchable
    blocks2, cached2 = km.match(STREAM[:12], max_tokens=11)
    assert cached2 == 6 and blocks2[1] == tail


def test_shared_block_never_recycled_while_referenced():
    km = BlockManager(4, block_tokens=4)
    assert km.ensure(1, 8)
    km.register(1, STREAM[:8])
    km.release(1)
    blocks, cached = km.match(STREAM[:9], max_tokens=8)
    km.adopt(2, blocks, cached)                  # holds both cached pages
    # pool pressure: only 2 free blocks remain; a 3-block ask must fail
    # rather than recycle the referenced cache
    assert not km.ensure(3, 12)
    assert km.ensure(3, 8)
    km.check_invariants()
    assert set(km.seqs[2].blocks).isdisjoint(km.seqs[3].blocks)


def test_lru_reclaims_oldest_cold_blocks_first():
    km = BlockManager(4, block_tokens=4)
    assert km.ensure(1, 4)
    km.register(1, STREAM[:4])
    km.release(1)
    first = km._keys and list(km._lru)[0]
    assert km.ensure(2, 4)
    km.register(2, STREAM[100:104])
    km.release(2)
    assert list(km._lru)[0] == first             # oldest release in front
    assert km.ensure(3, 12)                      # forces ONE reclaim
    km.check_invariants()
    assert km.reclaimed_blocks == 1
    # the younger entry survived
    blocks, cached = km.match(STREAM[100:104], max_tokens=3)
    assert cached == 3


def test_swap_roundtrip_drops_sharing_but_keeps_cache():
    km = BlockManager(8, block_tokens=4)
    assert km.ensure(1, 6)
    km.register(1, STREAM[:6])
    km.release(1)
    blocks, cached = km.match(STREAM[:12], max_tokens=11)
    km.adopt(2, blocks, cached)
    assert km.ensure(2, 10)
    moved = km.swap_out(2)
    assert moved > 0
    km.check_invariants()
    assert km.reclaimable_blocks == 2            # cached pages went cold
    assert km.swap_in(2) == moved
    km.check_invariants()
    # restored allocation is private; cache entries still valid
    assert all(km.refcnt[b] == 1 for b in km.seqs[2].blocks)
    assert km.match(STREAM[:6], max_tokens=5)[1] == 5


def test_hash_chain_is_content_and_position_sensitive():
    a = page_hash_chain(STREAM[:12], 4)
    b = page_hash_chain(STREAM[:12], 4)
    assert a == b and len(a) == 3
    c = page_hash_chain(np.concatenate([[9], STREAM[:11]]), 4)
    assert a[0] != c[0] and a[1] != c[1]         # shift poisons the chain


# ---------------------------------------------------------------------------
# Property test: random alloc/share/release/swap/reclaim sequences
# ---------------------------------------------------------------------------
@settings(max_examples=60, deadline=None)
@given(ops=st.lists(st.integers(0, 2 ** 20 - 1), min_size=1, max_size=120))
def test_blockmanager_refcount_invariants(ops):
    km = BlockManager(12, block_tokens=4)
    next_rid, live = 1, []
    for op in ops:
        kind = op % 6
        arg = op // 6
        if kind == 0:                            # admit: match+adopt+ensure
            rid = next_rid
            next_rid += 1
            length = arg % 37 + 2
            start = 0 if arg % 3 else 64         # two prefix families
            toks = STREAM[start:start + length]
            blocks, cached = km.match(toks, max_tokens=length - 1)
            if cached > 0:
                km.adopt(rid, blocks, cached)
            if km.ensure(rid, length):
                live.append((rid, start, length))
            elif cached > 0:
                km.release(rid)
            elif rid in km.seqs:                 # adopt-only, grow failed
                km.release(rid)
        elif live:
            idx = arg % len(live)
            rid, start, length = live[idx]
            a = km.seqs.get(rid)
            if kind == 1 and a and not a.swapped:      # grow + COW append
                res = km.fork_for_append(rid, max(a.tokens - 1, 0))
                if res is not None:
                    km.ensure(rid, a.tokens + arg % 9)
                    live[idx] = (rid, start, km.seqs[rid].tokens)
            elif kind == 2:                      # finish: register + release
                if a and not a.swapped:
                    km.register(rid, STREAM[start:start + a.tokens],
                                boundaries=(max(a.tokens - 2, 1),))
                km.release(rid)
                live.pop(idx)
            elif kind == 3:
                km.swap_out(rid)
            elif kind == 4:
                km.swap_in(rid)
            else:                                # abandon without register
                km.release(rid)
                live.pop(idx)
        km.check_invariants()
        used = km.num_blocks - len(km.free) - km.reclaimable_blocks
        assert used + len(km.free) + km.reclaimable_blocks == km.num_blocks


# ---------------------------------------------------------------------------
# Engine-level: acceptance criteria on the sim backend
# ---------------------------------------------------------------------------
def _run_scenario(scenario, cache, **kw):
    spec = WorkloadSpec(scenario=scenario, seed=0, system_prompt_len=64,
                        shared_system_frac=0.5, **kw)
    return run(ExperimentSpec(
        scheduler="sarathi", workload=spec,
        engine=EngineConfig(prefix_cache=cache), warmup=0))


def test_multiturn_prefix_cache_cuts_prefill_and_keeps_goodput():
    """Acceptance: ≥30% fewer prefill tokens computed, goodput not reduced,
    identical request outcomes (fixed seed, sim backend)."""
    on = _run_scenario("multiturn", True, rate=1.0, duration=120.0)
    off = _run_scenario("multiturn", False, rate=1.0, duration=120.0)
    assert on.n_finished == off.n_finished
    assert on.prefill_tokens <= 0.7 * off.prefill_tokens
    assert on.goodput_frac >= off.goodput_frac - 1e-9
    assert on.prefix_hits > 0 and on.cached_tokens > 0
    assert on.prefix_hit_rate > 0.5
    assert 0.3 <= on.cached_frac <= 1.0
    assert off.prefix_hits == 0 and off.cached_tokens == 0


def test_agentic_chains_reuse_previous_stage_context():
    on = _run_scenario("agentic", True, rate=0.4, duration=80.0)
    off = _run_scenario("agentic", False, rate=0.4, duration=80.0)
    assert on.n_finished == off.n_finished
    assert on.prefill_tokens <= 0.7 * off.prefill_tokens
    assert on.goodput_frac >= off.goodput_frac - 1e-9
    assert on.prefix_hits > 0


def test_prefix_cache_noop_without_identity():
    """Legacy workloads carry no prompt_tokens: cache on must be
    bit-identical to cache off."""
    spec = WorkloadSpec(rate=2.0, duration=30.0, seed=5)
    on = run(ExperimentSpec(scheduler="sarathi", workload=spec,
                            engine=EngineConfig(prefix_cache=True),
                            warmup=0))
    off = run(ExperimentSpec(scheduler="sarathi", workload=spec,
                             engine=EngineConfig(prefix_cache=False),
                             warmup=0))
    assert on.prefix_lookups == 0
    assert on.service_gain == pytest.approx(off.service_gain)
    assert on.makespan == pytest.approx(off.makespan)


def test_cached_len_charges_only_uncached_suffix():
    """A hit request's prefill_remaining — hence density/TTFT urgency and
    remaining-time estimates — counts only the suffix."""
    from repro.serving.request import Request, SLOSpec
    eng = ServeEngine(SimBackend.for_model("llama-8b"),
                      make_scheduler("sarathi"),
                      EngineConfig(kv_blocks=64))
    toks = STREAM[:300]
    donor = Request(rid=1, app="chatbot", arrival=0.0, prompt_len=300,
                    true_output_len=4, slo=SLOSpec("throughput"))
    donor.meta["prompt_tokens"] = toks
    donor.decoded = 4
    assert eng.kv.ensure(1, 304)
    eng.requests[1] = donor
    eng._prefix_register(donor)
    eng.kv.release(1)
    follow = Request(rid=2, app="chatbot", arrival=0.0, prompt_len=310,
                     true_output_len=4, slo=SLOSpec("throughput"))
    follow.meta["prompt_tokens"] = np.concatenate([toks, STREAM[500:510]])
    eng.requests[2] = follow
    eng._prefix_lookup(follow)
    # 2 full 128-token pages + the 44-token prompt-boundary tail
    assert follow.cached_len == 300
    assert follow.prefilled == 300
    assert follow.prefill_remaining == 10


def test_kv_free_frac_counts_reclaimable_cache():
    """Cold cache must not read as KV pressure (phantom-pressure fix)."""
    eng = ServeEngine(SimBackend.for_model("llama-8b"),
                      make_scheduler("sarathi"), EngineConfig(kv_blocks=8))
    assert eng.kv.ensure(1, 8 * 128)             # whole pool
    from repro.serving.request import Request, SLOSpec
    r = Request(rid=1, app="c", arrival=0.0, prompt_len=8 * 128,
                true_output_len=2, slo=SLOSpec("throughput"))
    r.decoded = 2
    r.meta["prompt_tokens"] = (np.arange(8 * 128) % 256)
    eng.requests[1] = r
    eng._prefix_register(r)
    eng.kv.release(1)
    assert len(eng.kv.free) == 0                 # all blocks are cold cache
    assert eng._view().kv_free_frac == pytest.approx(1.0)


def test_prefix_affinity_router_sticks_sessions():
    from repro.cluster.engine import ClusterEngine
    from repro.cluster.router import make_router

    spec = WorkloadSpec(scenario="multiturn", rate=1.5, duration=40.0,
                        seed=2, system_prompt_len=64,
                        shared_system_frac=0.0)
    gen = WorkloadGen(spec)
    engines = {}

    def factory(rid):
        engines[rid] = ServeEngine(SimBackend.for_model("llama-8b"),
                                   make_scheduler("sarathi"),
                                   EngineConfig(), workload=gen)
        return engines[rid]

    cluster = ClusterEngine(factory, make_router("prefix-affinity"),
                            n_replicas=2)
    fin = cluster.run(gen.arrival_stream())
    sess_homes = {}
    for rid, reqs in fin.items():
        for r in reqs:
            sess_homes.setdefault(r.session_id, set()).add(rid)
    assert len(sess_homes) > 5
    single_home = sum(1 for v in sess_homes.values() if len(v) == 1)
    assert single_home / len(sess_homes) >= 0.9  # sessions stick
    assert all(len(reqs) > 0 for reqs in fin.values())  # both replicas used
    # stickiness converts into real cache hits on the home replica
    assert sum(e.prefix_hits for e in engines.values()) > 10


def test_predictor_refits_via_samples_since_fit_counter():
    """Stale-predictor bug: observe() appends 1-4 samples per request, so a
    ``len(_y) % 2048 == 0`` gate is routinely stepped over.  The counter
    must trigger a refit after ~2048 new samples regardless of alignment."""
    from repro.core.scheduler import EngineView
    sched = make_scheduler("tempo")
    gen = WorkloadGen(WorkloadSpec(seed=11))
    sched.predictor.warm_start(gen.warmup_requests(600))
    fits0 = sched.predictor.fits
    assert fits0 >= 1
    view = EngineView(now=0.0, step=0, requests={}, max_batch=8,
                      prefill_budget=512)
    for r in gen.warmup_requests(600):           # 600 × ~4 samples > 2048
        sched.on_finish(r, view)
    assert sched.predictor.fits > fits0
    assert sched.predictor._since_fit < 2048
