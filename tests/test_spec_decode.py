"""Speculative decoding (DESIGN.md §11): drafter, on-device verification,
COW rollback invariants, engine accounting, and the invariant the whole
subsystem exists to uphold — spec-on token streams are byte-identical to
spec-off at any draft depth, because verification re-samples every
position with the same (seed, rid, pos)-keyed sampler the sequential
path uses."""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.baselines import make_scheduler
from repro.core.slo_tracker import StepCostModel
from repro.serving.backend import Sampler, SimBackend
from repro.serving.drafter import NgramDrafter, NullDrafter
from repro.serving.engine import (SPEC_EWMA_FLOOR, EngineConfig,
                                  ServeEngine)
from repro.serving.kvcache import BlockManager
from repro.serving.request import Request, SLOSpec
from repro.serving.run import BackendSpec, ExperimentSpec, run
from repro.serving.workload import WorkloadSpec


# ---------------------------------------------------------------------------
# Drafter
# ---------------------------------------------------------------------------
def test_ngram_drafter_proposes_continuation():
    # history repeats [5, 6, 7, 8]; suffix [7, 8] matched at its earlier
    # occurrence proposes what followed it
    toks = [5, 6, 7, 8, 9, 5, 6, 7, 8]
    assert NgramDrafter(nmax=3).propose(toks, 3) == [9, 5, 6]
    assert NgramDrafter(nmax=3).propose(toks, 1) == [9]
    assert NgramDrafter(nmax=3).propose(toks, 0) == []


def test_ngram_drafter_prefers_longest_match():
    # suffix [1, 2, 3] occurs earlier (-> 7); the 1-gram [3] also occurs
    # with a different continuation — the longer match must win
    toks = [1, 2, 3, 7, 3, 9, 1, 2, 3]
    assert NgramDrafter(nmax=3, nmin=1).propose(toks, 1) == [7]


def test_ngram_drafter_nmin_floors_match_length():
    # ONLY a unigram match exists: precision default (nmin=2) proposes
    # nothing; nmin=1 recovers the greedy fallback
    toks = [1, 2, 3, 4, 2]
    assert NgramDrafter(nmin=2).propose(toks, 4) == []
    assert NgramDrafter(nmin=1).propose(toks, 4) == [3, 4, 2]


def test_ngram_drafter_uses_most_recent_occurrence():
    toks = [4, 4, 1, 4, 4, 2, 4, 4]
    # suffix [4, 4]: occurrences at 0 (-> 1) and 3 (-> 2); latest wins
    assert NgramDrafter().propose(toks, 1) == [2]


def test_null_drafter_and_degenerate_histories():
    assert NullDrafter().propose([1, 2, 3], 4) == []
    assert NgramDrafter().propose([], 4) == []
    assert NgramDrafter().propose([7], 4) == []


# ---------------------------------------------------------------------------
# On-device accept/reject
# ---------------------------------------------------------------------------
def _verify(drafts_by_lane, targets_by_lane, V=16):
    """Run Sampler.verify_device on synthetic logits whose greedy argmax
    at window row s is targets[s]."""
    import jax.numpy as jnp
    B = len(drafts_by_lane)
    W = 1 + max(len(d) for d in drafts_by_lane)
    logits = np.full((B, W, V), -1.0, np.float32)
    inputs = np.zeros((B, W), np.int32)
    widths = np.zeros((B,), np.int32)
    for b, (dr, tg) in enumerate(zip(drafts_by_lane, targets_by_lane)):
        widths[b] = 1 + len(dr)
        inputs[b, 1:1 + len(dr)] = dr
        for s, t in enumerate(tg):
            logits[b, s, t] = 1.0
    tg, em = Sampler().verify_device(
        jnp.asarray(logits), jnp.asarray(inputs),
        jnp.asarray(np.arange(1, B + 1, dtype=np.int32)),
        jnp.asarray(np.zeros(B, np.int32)), jnp.asarray(widths))
    return np.asarray(tg), np.asarray(em)


def test_verify_device_accept_prefix_semantics():
    # lane 0: all 3 drafts match -> 4 emitted; lane 1: first draft wrong
    # -> only the bonus token; lane 2: match, mismatch, match -> the
    # trailing match must NOT count (leading run only)
    tg, em = _verify(drafts_by_lane=[[3, 4, 5], [9, 4, 5], [3, 9, 5]],
                     targets_by_lane=[[3, 4, 5, 6]] * 3)
    assert list(em) == [4, 1, 2]
    assert list(tg[0, :4]) == [3, 4, 5, 6]
    assert tg[1, 0] == 3 and tg[2, 1] == 4


def test_verify_device_width_masks_padding():
    # lane 1's single draft matches; the padded rows beyond its width
    # hold input 0 == target 0 by construction and must not be counted
    tg, em = _verify(drafts_by_lane=[[0, 0, 0], [0]],
                     targets_by_lane=[[0, 0, 0, 0], [0, 0]])
    assert list(em) == [4, 2]


def test_verify_device_single_row_window():
    tg, em = _verify(drafts_by_lane=[[]], targets_by_lane=[[7]])
    assert list(em) == [1] and tg[0, 0] == 7


# ---------------------------------------------------------------------------
# COW rollback: verify-window alloc + truncate keeps the pool sound
# ---------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(steps=st.lists(st.tuples(st.integers(0, 3),     # lane
                                st.integers(0, 8),     # granted depth
                                st.integers(0, 8)),    # accepted <= depth
                      min_size=1, max_size=40),
       page=st.sampled_from([4, 8]))
def test_verify_truncate_roundtrip_invariants(steps, page):
    """The engine's verify-step KV protocol — grow the allocation by the
    drafted window, then truncate back to the accepted prefix (any accept
    length, including 0) — must preserve refcount/ownership invariants
    for arbitrary interleavings across lanes, including COW-shared
    prompt pages and pool-pressure fallbacks."""
    bm = BlockManager(num_blocks=24, block_tokens=page)
    prompt = [7] * (2 * page)
    reqs = {}
    for rid in range(4):
        # lanes 1..3 adopt lane 0's registered prompt pages when cached
        blocks, cached = bm.match(prompt)
        if blocks:
            bm.adopt(rid, blocks, cached)
            bm.seqs[rid].tokens = cached
        if not bm.ensure(rid, len(prompt)):
            bm.release(rid)
            continue
        if rid == 0:
            bm.register(rid, prompt)
        reqs[rid] = len(prompt)      # accepted-token watermark
        bm.check_invariants()
    for lane, depth, acc in steps:
        if lane not in reqs:
            continue
        rid, tokens = lane, reqs[lane]
        acc = min(acc, depth)
        # drafted window: +1 mandatory token + depth draft slots, COW-
        # forking the shared tail page before any append lands in it
        fork = bm.fork_for_append(rid, tokens)
        if fork is None:
            continue
        if not bm.ensure(rid, tokens + 1 + depth):
            continue
        bm.check_invariants()
        reqs[lane] = tokens + 1 + acc
        bm.truncate(rid, reqs[lane])
        bm.check_invariants()
        assert len(bm.seqs[rid].blocks) == -(-reqs[lane] // page)
    for rid in list(reqs):
        bm.release(rid)
        bm.check_invariants()
    assert bm.used_blocks == 0


# ---------------------------------------------------------------------------
# Cost model: the verify-token feature
# ---------------------------------------------------------------------------
def test_cost_model_prices_verify_tokens():
    """Regression for the mis-attribution bug: without the v feature,
    verify-step time was blamed on decode batch size and corrupted plain
    decode predictions.  Fit on a mix of plain and verify steps drawn
    from a known linear model and check both step kinds predict true."""
    cm = StepCostModel(min_samples=16, refit_every=16)
    rng = np.random.default_rng(0)
    t_of = lambda d, ctx, v: 0.004 + 2e-4 * d + 1e-6 * ctx + 3e-4 * v
    for _ in range(120):
        d = int(rng.integers(1, 9))
        ctx = float(rng.integers(100, 2000))
        v = int(rng.integers(0, 5)) * 8 if rng.random() < 0.5 else 0
        cm.observe(t_of(d, ctx, v), 0, d, ctx, verify_tokens=v)
    assert cm.fitted
    for d, ctx, v in ((4, 800, 0), (4, 800, 32), (8, 1500, 16)):
        pred = cm.predict(0, d, ctx, verify_tokens=v)
        assert pred == pytest.approx(t_of(d, ctx, v), rel=0.08)
    # the verify coefficient specifically: widening the window must cost
    assert cm.predict(0, 4, 800, verify_tokens=32) \
        > cm.predict(0, 4, 800, verify_tokens=0) + 5e-3


def test_cost_model_spec_off_unperturbed():
    """All-zero verify columns must leave the 5-feature fit intact."""
    cm = StepCostModel(min_samples=16, refit_every=16)
    for i in range(64):
        d = 1 + i % 8
        cm.observe(0.004 + 2e-4 * d + 1e-6 * 500, 0, d, 500.0)
    assert cm.predict(0, 4, 500.0) == pytest.approx(
        0.004 + 2e-4 * 4 + 1e-6 * 500, rel=0.05)


# ---------------------------------------------------------------------------
# Engine + SimBackend
# ---------------------------------------------------------------------------
def _sim_run(depth, accept=0.7, rate=2.0):
    return run(ExperimentSpec(
        scheduler="tempo",
        workload=WorkloadSpec(rate=rate, duration=10.0, seed=0),
        engine=EngineConfig(spec_depth_max=depth),
        backend=BackendSpec(kind=SimBackend.for_model(
            "llama-8b", spec_accept_rate=accept))))


def test_sim_spec_finishes_same_requests_faster():
    off, on = _sim_run(0), _sim_run(4)
    assert on.n_finished == off.n_finished
    assert on.spec_proposed > 0 and 0.0 < on.accept_rate < 1.0
    assert off.spec_proposed == 0 and off.accept_rate == 0.0
    # the sim clock is memory-bound at decode: emitting several tokens
    # per step must strictly shorten the run
    assert on.makespan < off.makespan


def test_engine_ewma_floor_stops_hopeless_lanes():
    """With a drafter the model never agrees with (accept_rate=0), each
    lane pays a bounded number of rejected windows before its EWMA falls
    under SPEC_EWMA_FLOOR and the engine stops granting it depth — total
    proposals stay O(lanes), not O(tokens)."""
    assert 0.0 < SPEC_EWMA_FLOOR < 1.0
    s = _sim_run(4, accept=0.0)
    # EWMA hits 0 after ONE fully-rejected window -> <= depth_max
    # proposals per admitted request
    assert 0 < s.spec_proposed <= 4 * s.n_admitted
    assert s.spec_accepted == 0


# ---------------------------------------------------------------------------
# jax backend: byte-identity and the partitioned dispatch
# ---------------------------------------------------------------------------
def _jax_backend(**kw):
    from repro.serving.jax_backend import PagedJaxBackend
    kw.setdefault("arch", "tinyllama-1.1b")
    kw.setdefault("num_blocks", 24)
    kw.setdefault("page", 16)
    kw.setdefault("max_len", 64)
    kw.setdefault("seed", 0)
    return PagedJaxBackend(**kw)


def _jax_streams(depth, decode_steps=1, **be_kw):
    be = _jax_backend(**be_kw)
    eng = ServeEngine(be, make_scheduler("tempo", use_predictor=False),
                      EngineConfig(max_batch=2, prefill_budget=16,
                                   spec_depth_max=depth,
                                   decode_steps=decode_steps))
    eng.load([Request(rid=i + 1, app="chatbot", arrival=0.0,
                      prompt_len=20 + 3 * i, true_output_len=12,
                      slo=SLOSpec("throughput", ttlt=1e6))
              for i in range(2)], [])
    fin = eng.run()
    assert len(fin) == 2
    return {r.rid: list(be.generated[r.rid]) for r in fin}, eng


def test_jax_spec_streams_byte_identical_across_horizons():
    """The tentpole invariant, end to end on real decoding: greedy
    streams at draft horizons 1/4/8 — and speculation composed with the
    multi-step scan — are byte-equal to plain sequential decode."""
    ref, _ = _jax_streams(0)
    for depth in (1, 4, 8):
        got, eng = _jax_streams(depth)
        assert got == ref, f"stream diverged at depth {depth}"
    got, eng = _jax_streams(4, decode_steps=4)
    assert got == ref
    assert eng.spec_proposed > 0


def test_jax_spec_accounting_consistent():
    _, eng = _jax_streams(4)
    assert eng.spec_proposed >= eng.spec_accepted >= 0
    assert eng.spec_proposed > 0
    # every emitted token is accounted once: 2 lanes x 12 tokens
    assert sum(len(t) for t in eng.backend.generated.values()) == 24


def test_jax_mixed_drafted_and_plain_lanes_partition():
    """Lanes granted depth 0 (or whose drafter proposes nothing) must
    ride the plain one-token dispatch, not a padded verify row — and the
    merged results must preserve lane order and stream content."""
    be = _jax_backend()
    reqs = [Request(rid=i + 1, app="chatbot", arrival=0.0,
                    prompt_len=18 + i, true_output_len=8,
                    slo=SLOSpec("throughput", ttlt=1e6))
            for i in range(3)]
    bm = BlockManager(num_blocks=be.num_blocks,
                      block_tokens=be.block_tokens)
    tabs = {}
    for r in reqs:
        assert bm.ensure(r.rid, r.prompt_len)
        tabs[r.rid] = bm.block_table(r.rid)
        be.prefill_chunk(r, 0, r.prompt_len, tabs[r.rid])
    # warm histories so the drafter has something to match
    for _ in range(4):
        be.decode_batch(reqs, [tabs[r.rid] for r in reqs])
        for r in reqs:
            r.decoded += 1
            assert bm.ensure(r.rid, r.prompt_len + r.decoded + 1)
            tabs[r.rid] = bm.block_table(r.rid)
    ref = {r.rid: list(be.generated[r.rid]) for r in reqs}
    # mixed dispatch: lane 1 is pinned to depth 0
    for r in reqs:
        assert bm.ensure(r.rid, r.prompt_len + r.decoded + 1 + 3)
        tabs[r.rid] = bm.block_table(r.rid)
    res = be.decode_verify_batch(reqs, [tabs[r.rid] for r in reqs],
                                 [3, 0, 3])
    assert res[1] == (1, 0, 0), "depth-0 lane must be a plain decode row"
    for r, (e, a, p) in zip(reqs, res):
        assert 1 <= e <= 4 and a == e - 1 and p <= 3
        got = list(be.generated[r.rid])
        assert got[:len(ref[r.rid])] == ref[r.rid]
        assert len(got) == len(ref[r.rid]) + e
        r.decoded += e
        bm.truncate(r.rid, r.prompt_len + r.decoded)
        bm.check_invariants()


def test_jax_null_drafter_degrades_to_plain_decode():
    """With a drafter that never proposes, the verify path must emit
    exactly one token per lane per step and count zero proposals."""
    ref, _ = _jax_streams(0)
    got, eng = _jax_streams(4, drafter=NullDrafter())
    assert got == ref
    assert eng.spec_proposed == 0 and eng.spec_accepted == 0


def test_jax_spec_streams_invariant_under_telemetry():
    """Attaching the metrics registry + tracer must not perturb spec
    scheduling or token content (observability is read-only)."""
    from repro.obs import MetricsRegistry, Tracer
    ref, _ = _jax_streams(4)
    be = _jax_backend()
    obs, tr = MetricsRegistry(), Tracer()
    eng = ServeEngine(be, make_scheduler("tempo", use_predictor=False),
                      EngineConfig(max_batch=2, prefill_budget=16,
                                   spec_depth_max=4),
                      obs=obs, tracer=tr)
    eng.load([Request(rid=i + 1, app="chatbot", arrival=0.0,
                      prompt_len=20 + 3 * i, true_output_len=12,
                      slo=SLOSpec("throughput", ttlt=1e6))
              for i in range(2)], [])
    eng.run()
    assert {r: list(t) for r, t in be.generated.items()} == ref
    names = {m.name for m in obs.instruments()}
    assert {"engine_spec_proposed_total", "engine_spec_accepted_total",
            "engine_spec_accept_rate"} <= names
    kinds = {e["name"] for e in tr.events}
    assert {"spec_draft", "spec_verify"} <= kinds
