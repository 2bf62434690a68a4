"""Grouped-margin goodput scheduler: group-assignment properties, JIT
deferral safety, decision invariants, determinism, shedding, and the
arrival-visibility fix shared with Tempo."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.baselines import make_scheduler
from repro.core.gmg import (GROUP_RANK, GROUPS, GroupedMarginScheduler,
                            classify_margin)
from repro.core.scheduler import EngineView, TempoScheduler
from repro.serving.request import ReqState, Request, SLOSpec

KINDS = ["latency", "throughput", "collective", "none"]


def _mk_requests(n, seed):
    rng = np.random.default_rng(seed)
    reqs = {}
    for i in range(1, n + 1):
        kind = KINDS[int(rng.integers(0, 4))]
        r = Request(rid=i, app="chatbot", arrival=float(rng.uniform(0, 10)),
                    prompt_len=int(rng.integers(4, 500)),
                    true_output_len=int(rng.integers(8, 800)),
                    slo=SLOSpec(kind))
        r.prefilled = int(rng.integers(0, r.prompt_len + 1))
        if r.prefilled == r.prompt_len:
            r.decoded = int(rng.integers(0, r.true_output_len))
            if r.decoded:
                r.first_token_t = r.arrival + 0.5
                r.token_times = list(
                    r.arrival + 0.5 + 0.05 * np.arange(r.decoded))
        r.pred_upper = float(r.true_output_len * rng.uniform(0.5, 3.0))
        reqs[i] = r
    return reqs


def _view(reqs, now=12.0, step=40, max_batch=8, budget=512):
    return EngineView(now=now, step=step, requests=reqs,
                      max_batch=max_batch, prefill_budget=budget)


def _check_decision(dec, view):
    assert len(dec.decode_ids) <= view.max_batch
    assert len(set(dec.decode_ids)) == len(dec.decode_ids)
    for rid in dec.decode_ids:
        r = view.requests[rid]
        assert r.prefill_remaining == 0 and not r.done
    assert sum(dec.prefill.values()) <= view.prefill_budget
    for rid, chunk in dec.prefill.items():
        r = view.requests[rid]
        assert 0 < chunk <= r.prefill_remaining
    assert not (set(dec.shed) & set(dec.decode_ids))
    assert not (set(dec.shed) & set(dec.prefill))


# ---------------------------------------------------------------------------
# group-assignment properties (pure function)
# ---------------------------------------------------------------------------
@settings(max_examples=200, deadline=None)
@given(m1=st.floats(-100.0, 100.0), m2=st.floats(-100.0, 100.0),
       need=st.floats(0.01, 50.0), gain=st.floats(0.0, 1.0))
def test_group_assignment_monotone_in_margin(m1, m2, need, gain):
    """For fixed (need, gain_frac), more margin can never move a request
    to a TIGHTER group."""
    lo, hi = min(m1, m2), max(m1, m2)
    g_lo = classify_margin(lo, need, gain)
    g_hi = classify_margin(hi, need, gain)
    assert GROUP_RANK[g_lo] <= GROUP_RANK[g_hi]


@settings(max_examples=200, deadline=None)
@given(margin=st.floats(-100.0, 100.0), need=st.floats(0.01, 50.0),
       gain=st.floats(0.0, 1.0))
def test_group_boundaries(margin, need, gain):
    g = classify_margin(margin, need, gain)
    assert g in GROUPS
    if g == "slack":
        # JIT deferral safety: a deferred request ALWAYS still fits its
        # budget — slack requires margin >= slack_frac*need > 0, i.e.
        # remaining-time estimate strictly below the remaining budget
        assert margin > 0
        assert margin >= 2.0 * need          # default slack_frac
    if g == "hopeless":
        assert margin < 0 and gain < 0.05
    if margin < 0 and gain >= 0.05:
        assert g == "late"


def test_jit_deferral_never_outlives_budget():
    """Runtime check: whenever gmg declines to schedule a decodable SLO
    request (defers it), that request's conservative remaining-time
    estimate must still fit its remaining budget — deferral may spend
    slack, never cross into lateness."""
    from repro.serving.engine import EngineConfig, ServeEngine, SimBackend
    from repro.serving.workload import WorkloadGen, WorkloadSpec
    sched = make_scheduler("gmg")
    spec = WorkloadSpec(rate=5.0, duration=12.0, seed=7)
    gen = WorkloadGen(spec)
    sched.predictor.warm_start(gen.warmup_requests(128))
    eng = ServeEngine(SimBackend.for_model("llama-8b"), sched,
                      EngineConfig(max_batch=16), workload=gen)
    singles, dags = gen.generate()
    eng.load(singles, dags)
    violations = []
    orig = sched.schedule

    def checked(view):
        dec = orig(view)
        chosen = set(dec.decode_ids)
        for r in view.requests.values():
            if r.state == ReqState.FINISHED or r.done \
                    or r.prefill_remaining > 0 or r.slo.kind == "none" \
                    or r.rid in chosen:
                continue
            gi = sched._ginfo.get(r.rid)
            if gi is None or gi.group != "slack":
                continue           # only JIT deferral is under test
            eff = gi.effective_margin(view.now)
            if eff < 0:
                violations.append((view.now, r.rid, eff))
        return dec

    sched.schedule = checked
    eng.run()
    assert eng.finished
    assert not violations, violations[:5]


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 40),
       step=st.integers(0, 100))
def test_gmg_decision_invariants(seed, n, step):
    reqs = _mk_requests(n, seed)
    sched = GroupedMarginScheduler(use_predictor=False)
    view = _view(reqs, step=step)
    for r in reqs.values():
        sched.on_arrival(r, view)
    dec = sched.schedule(view)
    _check_decision(dec, view)
    # schedule() must stay valid on repeated calls (cached state)
    dec2 = sched.schedule(_view(reqs, now=12.5, step=step + 1))
    _check_decision(dec2, _view(reqs))


def test_gmg_deterministic_sim_vs_sim():
    """Two fresh engines over the same seeded workload must produce
    byte-identical schedules: same finish order, same token times."""
    from repro.serving.run import run_experiment
    from repro.serving.workload import WorkloadSpec

    def go():
        from repro.core.service import ServiceModel
        from repro.serving.engine import (EngineConfig, ServeEngine,
                                          SimBackend)
        from repro.serving.workload import WorkloadGen
        spec = WorkloadSpec(rate=6.0, duration=10.0, seed=11)
        gen = WorkloadGen(spec)
        sched = make_scheduler("gmg", service=ServiceModel())
        sched.predictor.warm_start(gen.warmup_requests(128))
        eng = ServeEngine(SimBackend.for_model("llama-8b"), sched,
                          EngineConfig(), workload=gen)
        singles, dags = gen.generate()
        eng.load(singles, dags)
        fin = eng.run()
        return [(r.rid, r.finish_t, tuple(r.token_times[:3])) for r in fin]

    assert go() == go()


def test_gmg_reserve_serves_best_effort():
    reqs = {}
    for i in range(1, 12):
        r = Request(rid=i, app="code", arrival=0.0, prompt_len=1,
                    true_output_len=100,
                    slo=SLOSpec("throughput", ttlt=5.0))
        r.prefilled = 1
        reqs[i] = r
    be = Request(rid=99, app="batch", arrival=0.0, prompt_len=1,
                 true_output_len=100, slo=SLOSpec("none"))
    be.prefilled = 1
    reqs[99] = be
    sched = GroupedMarginScheduler(use_predictor=False, reserve=0.1)
    view = _view(reqs, max_batch=8)
    for r in reqs.values():
        sched.on_arrival(r, view)
    dec = sched.schedule(view)
    assert 99 in dec.decode_ids        # starvation reserve admits non-SLO


def test_gmg_latency_pacing_defers_ahead_of_schedule():
    """Same behaviour Tempo pins down: an ahead-of-timeline latency stream
    yields its slot to deadline work when slots are scarce."""
    now = 10.0
    r = Request(rid=1, app="chatbot", arrival=0.0, prompt_len=4,
                true_output_len=500, slo=SLOSpec("latency", tbt=0.5))
    r.prefilled = 4
    r.decoded = 10
    r.first_token_t = 1.0
    r.token_times = [now - 0.01]       # token JUST emitted -> way ahead
    comp = Request(rid=2, app="code", arrival=0.0, prompt_len=4,
                   true_output_len=500, slo=SLOSpec("throughput", ttlt=30.0))
    comp.prefilled = 4
    reqs = {1: r, 2: comp}
    sched = GroupedMarginScheduler(use_predictor=False)
    view = _view(reqs, now=now, max_batch=1, step=0)
    for x in reqs.values():
        sched.on_arrival(x, view)
    dec = sched.schedule(view)
    assert dec.decode_ids == [2]       # paced latency yields the slot
    # once the token is overdue, it takes the slot back
    r.token_times = [now - 0.49]
    sched2 = GroupedMarginScheduler(use_predictor=False)
    for x in reqs.values():
        sched2.on_arrival(x, view)
    dec2 = sched2.schedule(view)
    assert dec2.decode_ids[0] == 1


def test_gmg_sheds_hopeless_under_kv_pressure():
    """A hopelessly-late request must be dropped (Decision.shed) when KV
    headroom is gone — and never a collective sibling."""
    now = 1000.0
    hopeless = Request(rid=1, app="code", arrival=0.0, prompt_len=64,
                       true_output_len=4000,
                       slo=SLOSpec("throughput", ttlt=5.0))  # long dead
    hopeless.prefilled = 64
    hopeless.pred_upper = 4000.0
    coll = Request(rid=2, app="math", arrival=0.0, prompt_len=64,
                   true_output_len=4000,
                   slo=SLOSpec("collective", ttlt=5.0), dag_id=7)
    coll.prefilled = 64
    coll.pred_upper = 4000.0
    ok = Request(rid=3, app="code", arrival=now - 0.5, prompt_len=16,
                 true_output_len=32, slo=SLOSpec("throughput", ttlt=30.0))
    ok.prefilled = 16
    ok.pred_upper = 32.0
    reqs = {1: hopeless, 2: coll, 3: ok}
    sched = GroupedMarginScheduler(use_predictor=False)
    view = EngineView(now=now, step=0, requests=reqs, max_batch=4,
                      prefill_budget=64, kv_free_frac=0.01)
    for x in reqs.values():
        sched.on_arrival(x, view)
    dec = sched.schedule(view)
    assert 1 in dec.shed
    assert 2 not in dec.shed           # collectives are never shed
    assert 3 not in dec.shed
    # without pressure: no shedding, hopeless may still backfill
    sched2 = GroupedMarginScheduler(use_predictor=False)
    view2 = EngineView(now=now, step=0, requests=reqs, max_batch=4,
                       prefill_budget=64, kv_free_frac=0.9)
    for x in reqs.values():
        sched2.on_arrival(x, view2)
    assert not sched2.schedule(view2).shed


def test_engine_accounts_shed_requests():
    """End-to-end: an engine driven into KV pressure with a hopeless
    request reports it via eng.shed, and the summary counts it as a miss
    (denominator = admitted, not finished)."""
    from repro.core.service import ServiceModel
    from repro.serving.engine import EngineConfig, ServeEngine, SimBackend
    from repro.serving.metrics import summarize
    eng = ServeEngine(SimBackend.for_model("llama-8b"),
                      make_scheduler("gmg", use_predictor=False),
                      EngineConfig(max_batch=4, kv_blocks=24))
    slo = SLOSpec("throughput", ttlt=2.0)
    # a dead-on-arrival long request (deadline in the past relative to its
    # service need) plus live short ones to create competition
    dead = Request(rid=1, app="code", arrival=0.0, prompt_len=256,
                   true_output_len=3000, slo=slo)
    live = [Request(rid=i, app="code", arrival=0.1, prompt_len=512,
                    true_output_len=64,
                    slo=SLOSpec("throughput", ttlt=60.0))
            for i in range(2, 6)]
    eng.load([dead] + live, [])
    fin = eng.run()
    s = summarize("gmg", fin, ServiceModel(), eng.now,
                  n_admitted=eng.admitted_count, shed=eng.shed)
    assert s.n_admitted == 5
    assert s.n_finished + s.n_shed + s.n_unfinished >= 5
    if eng.shed:                        # pressure materialised
        assert s.n_shed == len(eng.shed)
        assert s.goodput_frac < 1.0     # shed counts as a miss


# ---------------------------------------------------------------------------
# arrival-visibility fix (Tempo + gmg)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["tempo", "gmg"])
def test_fresh_arrival_prefills_immediately(name):
    """Regression: a request admitted right after a priority refresh used
    to be invisible to the prefill loop for up to 5 steps (until the
    dirty-refresh backoff elapsed) even with the whole budget idle."""
    if name == "tempo":
        sched = TempoScheduler(use_predictor=False)
    else:
        sched = GroupedMarginScheduler(use_predictor=False)
    old = Request(rid=1, app="code", arrival=0.0, prompt_len=4,
                  true_output_len=400, slo=SLOSpec("throughput", ttlt=30.0))
    old.prefilled = 4
    reqs = {1: old}
    view0 = _view(reqs, now=1.0, step=10)
    sched.on_arrival(old, view0)
    sched.schedule(view0)              # refresh happens here
    # new request arrives ONE step later — well inside the quanta window
    fresh = Request(rid=2, app="code", arrival=1.01, prompt_len=300,
                    true_output_len=100,
                    slo=SLOSpec("throughput", ttlt=30.0))
    reqs[2] = fresh
    view1 = _view(reqs, now=1.02, step=11)
    sched.on_arrival(fresh, view1)
    dec = sched.schedule(view1)
    assert dec.prefill.get(2, 0) > 0, \
        f"{name}: fresh arrival invisible to the prefill loop"


def test_margin_summary_published():
    reqs = _mk_requests(12, 5)
    sched = GroupedMarginScheduler(use_predictor=False)
    view = _view(reqs)
    for r in reqs.values():
        sched.on_arrival(r, view)
    sched.schedule(view)
    ms = sched.margin_summary
    assert set(ms["counts"]) == set(GROUPS)
    n_slo = sum(1 for r in reqs.values()
                if r.state != ReqState.FINISHED and r.slo.kind != "none")
    assert sum(ms["counts"].values()) == n_slo
    assert ms["lateness"] >= 0.0


def test_release_of_swapped_sequence_drops_swapped_tokens():
    """Regression: shedding a preempted (swapped-out) request releases its
    host copy — BlockManager.swapped_tokens must come back down instead of
    drifting upward for the rest of the run."""
    from repro.serving.kvcache import BlockManager
    kv = BlockManager(num_blocks=8, block_tokens=16)
    assert kv.ensure(1, 40)
    kv.swap_out(1)
    assert kv.swapped_tokens == 40
    kv.release(1)
    assert kv.swapped_tokens == 0
    assert 1 not in kv.seqs
