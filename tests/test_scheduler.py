"""Scheduler invariants: Tempo + baselines produce valid Decisions under
arbitrary request states (hypothesis), pacing/reserve/preemption behaviours."""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.baselines import make_scheduler
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


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 40),
       step=st.integers(0, 100))
def test_tempo_decision_invariants(seed, n, step):
    reqs = _mk_requests(n, seed)
    sched = TempoScheduler(use_predictor=False)
    view = _view(reqs, step=step)
    for r in reqs.values():
        sched.on_arrival(r, view)
    dec = sched.schedule(view)
    _check_decision(dec, view)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), name=st.sampled_from(
    ["vllm", "sarathi", "autellix", "edf"]))
def test_baseline_decision_invariants(seed, name):
    reqs = _mk_requests(20, seed)
    sched = make_scheduler(name)
    view = _view(reqs)
    dec = sched.schedule(view)
    assert len(dec.decode_ids) <= view.max_batch
    for rid in dec.decode_ids:
        r = view.requests[rid]
        assert r.prefill_remaining == 0 and not r.done


def test_reserve_serves_best_effort():
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
    sched = TempoScheduler(use_predictor=False, reserve=0.1)
    view = _view(reqs, max_batch=8)
    for r in reqs.values():
        sched.on_arrival(r, view)
    dec = sched.schedule(view)
    assert 99 in dec.decode_ids        # starvation reserve admits non-SLO


def test_latency_pacing_defers_ahead_of_schedule():
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
    sched = TempoScheduler(use_predictor=False)
    view = _view(reqs, now=now, max_batch=1, step=0)
    for x in reqs.values():
        sched.on_arrival(x, view)
    dec = sched.schedule(view)
    assert dec.decode_ids == [2]       # paced latency yields the single slot
    # once the token is overdue, it takes the slot back
    r.token_times = [now - 0.49]
    sched2 = TempoScheduler(use_predictor=False)
    for x in reqs.values():
        sched2.on_arrival(x, view)
    dec2 = sched2.schedule(view)
    assert dec2.decode_ids[0] == 1


def test_collective_stage_uses_max_sibling_remaining():
    sched = TempoScheduler(use_predictor=False, precise=True)
    a = Request(rid=1, app="math", arrival=0.0, prompt_len=4,
                true_output_len=10, slo=SLOSpec("collective", ttlt=20.0),
                dag_id=7, stage=0)
    b = Request(rid=2, app="math", arrival=0.0, prompt_len=4,
                true_output_len=1000, slo=SLOSpec("collective", ttlt=20.0),
                dag_id=7, stage=0)
    a.prefilled = b.prefilled = 4
    reqs = {1: a, 2: b}
    long_remaining = 50.0
    view = EngineView(now=1.0, step=0, requests=reqs, max_batch=4,
                      prefill_budget=64,
                      dag_remaining=lambda rid: long_remaining)
    for x in reqs.values():
        sched.on_arrival(x, view)
    d_a = sched.density(a, view)
    view2 = EngineView(now=1.0, step=0, requests=reqs, max_batch=4,
                       prefill_budget=64, dag_remaining=lambda rid: 0.0)
    d_a_solo = sched.density(a, view2)
    assert d_a < d_a_solo              # stage-coupled density is throttled
