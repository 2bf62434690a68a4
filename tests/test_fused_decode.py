"""Fused multi-step decode (DESIGN.md §10).

Three layers under test:
  1. the fused append+attend Pallas kernel vs the two-dispatch reference
     (``paged_kv_append_batch`` + ``paged_attention``) — output AND page
     write-back parity in interpret mode, property-tested over batch
     width, context length, and page-boundary crossings;
  2. ``decode_batch_n``: n micro-steps in one ``lax.scan`` dispatch must
     emit byte-identical token streams to n single-step dispatches — at
     temperature 0 and seeded temperature>0, including lanes that retire
     mid-scan and KV that swaps out/in across a multi-step window;
  3. the engine fast path: runs with ``decode_steps`` n∈{2,4,8} must
     finish the same requests with the same streams (and the same
     per-token SLO accounting shape) as n=1, telemetry on or off.
"""

from hypothesis import given, settings, strategies as st

import jax.numpy as jnp
import numpy as np

from repro.core.baselines import make_scheduler
from repro.kernels.paged_attention import (fused_decode_attention,
                                           paged_attention,
                                           paged_kv_append_batch)
from repro.obs import MetricsRegistry
from repro.serving.engine import EngineConfig, ServeEngine
from repro.serving.jax_backend import PagedJaxBackend
from repro.serving.request import Request, SLOSpec


# ---------------------------------------------------------------------------
# 1. kernel parity: fused vs two-dispatch reference
# ---------------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(B=st.integers(1, 3), n_max=st.integers(1, 3),
       page=st.sampled_from([4, 8]), KV=st.sampled_from([1, 2]),
       G=st.sampled_from([1, 2]), seed=st.integers(0, 10**6))
def test_fused_kernel_matches_two_dispatch(B, n_max, page, KV, G, seed):
    D = 4
    H = KV * G
    P = B * n_max + 1                       # +1: scrap page at P-1
    rng = np.random.default_rng(seed)
    k_pages = rng.normal(size=(P, page, KV, D)).astype(np.float32)
    v_pages = rng.normal(size=(P, page, KV, D)).astype(np.float32)
    q = rng.normal(size=(B, H, D)).astype(np.float32)
    k_new = rng.normal(size=(B, KV, D)).astype(np.float32)
    v_new = rng.normal(size=(B, KV, D)).astype(np.float32)
    # disjoint tables; positions sweep page boundaries (0, page-1, page, …)
    tables = np.arange(B * n_max, dtype=np.int32).reshape(B, n_max)
    pos = rng.integers(0, n_max * page, size=B).astype(np.int32)

    kp, vp = paged_kv_append_batch(jnp.asarray(k_pages),
                                   jnp.asarray(v_pages),
                                   jnp.asarray(k_new), jnp.asarray(v_new),
                                   jnp.asarray(tables), jnp.asarray(pos))
    o_ref = paged_attention(jnp.asarray(q), kp, vp, jnp.asarray(tables),
                            jnp.asarray(pos + 1), interpret=True)
    o_fus, kf, vf = fused_decode_attention(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(tables),
        jnp.asarray(pos), interpret=True)

    np.testing.assert_allclose(np.asarray(o_fus), np.asarray(o_ref),
                               rtol=1e-5, atol=1e-5)
    # page write-back parity everywhere but the scrap page (the fused
    # kernel parks non-target cells' write-backs there)
    np.testing.assert_array_equal(np.asarray(kf)[:-1], np.asarray(kp)[:-1])
    np.testing.assert_array_equal(np.asarray(vf)[:-1], np.asarray(vp)[:-1])


def test_backend_fused_flag_streams_identical():
    """The backend's fused kernel and the reference two-dispatch path must
    decode identical greedy streams end-to-end (argmax sits far above ulp
    differences of the two attention orderings)."""
    streams = {}
    for fused in (True, False):
        be = PagedJaxBackend(num_blocks=16, page=16, max_len=64, seed=0,
                             fused=fused)
        eng = ServeEngine(be, make_scheduler("tempo", use_predictor=False),
                          EngineConfig(max_batch=4, prefill_budget=32))
        eng.load(_mk_reqs(n=2), [])
        fin = eng.run()
        streams[fused] = {r.rid: list(be.generated[r.rid]) for r in fin}
    assert streams[True] == streams[False]


# ---------------------------------------------------------------------------
# 2. decode_batch_n vs single-step dispatch
# ---------------------------------------------------------------------------
def _mk_reqs(n=2, prompt=30, out=10, kind="throughput"):
    return [Request(rid=i + 1, app="chatbot", arrival=0.0,
                    prompt_len=prompt, true_output_len=out,
                    slo=SLOSpec(kind, ttlt=1e6))
            for i in range(n)]


def test_multi_step_mid_scan_finish_matches_single_step():
    """Lanes with unequal remaining output retire inside the scan: their
    tokens stop (active mask false), KV writes reroute to scrap, and the
    surviving lane's stream equals the single-step reference."""
    def fresh():
        be = PagedJaxBackend(num_blocks=16, page=16, max_len=64, seed=0)
        r1 = _mk_reqs(n=1, prompt=8, out=2)[0]
        r2 = _mk_reqs(n=2, prompt=8, out=6)[1]
        be.prefill_chunk(r1, 0, 8, [0])
        be.prefill_chunk(r2, 0, 8, [1])
        return be, r1, r2

    be, r1, r2 = fresh()
    toks, act = be.decode_batch_n([r1, r2], [[0], [1]], 4)
    assert toks.shape == (2, 4) and act.shape == (2, 4)
    assert act.tolist() == [[True, True, False, False],
                            [True, True, True, True]]
    assert len(be.generated[1]) == 2 and len(be.generated[2]) == 4

    be2, s1, s2 = fresh()
    for _ in range(2):
        be2.decode_batch([s1, s2], [[0], [1]])
        s1.decoded += 1
        s2.decoded += 1
    for _ in range(2):
        be2.decode_batch([s2], [[1]])
        s2.decoded += 1
    assert be.generated == be2.generated


def _run_engine(decode_steps, num_blocks=16, temperature=0.0, top_k=0,
                out=10, obs=None):
    be = PagedJaxBackend(num_blocks=num_blocks, page=16, max_len=64,
                         seed=0, temperature=temperature, top_k=top_k)
    eng = ServeEngine(be, make_scheduler("tempo", use_predictor=False),
                      EngineConfig(max_batch=4, prefill_budget=32,
                                   decode_steps=decode_steps),
                      obs=obs)
    eng.load(_mk_reqs(n=3, prompt=20, out=out), [])
    fin = eng.run()
    assert len(fin) == 3
    return eng, be, {r.rid: list(be.generated[r.rid]) for r in fin}


def test_engine_decode_steps_byte_identical_greedy():
    eng1, be1, ref = _run_engine(1)
    for n in (2, 4, 8):
        engn, be, got = _run_engine(n)
        assert got == ref, f"decode_steps={n} changed the streams"
        # the fast path actually engaged: some dispatch ran n>1 micro-steps
        assert any(k > 1 for k in be._decode_n_cache), \
            f"decode_steps={n} never dispatched multi-step"
        # fewer engine->device decode dispatches, same tokens, and the SLO
        # accounting still sees one engine step per token window
        assert be.n_decode_dispatches < be1.n_decode_dispatches
        assert be.n_decode_tokens == be1.n_decode_tokens
        assert engn.step == eng1.step     # micro-steps counted 1:1
        assert len(engn.step_log) == engn.step


def test_engine_decode_steps_byte_identical_seeded_temperature():
    _, _, ref = _run_engine(1, temperature=0.8, top_k=20)
    _, _, got = _run_engine(4, temperature=0.8, top_k=20)
    assert got == ref


def test_engine_decode_steps_swap_across_window():
    """Tiny pool (4 pages for 2×40-token sequences): evictions interleave
    with multi-step windows; swap restore must stay byte-exact so streams
    equal the single-step run."""
    def run(decode_steps):
        be = PagedJaxBackend(num_blocks=4, page=16, max_len=64, seed=0)
        eng = ServeEngine(be, make_scheduler("tempo", use_predictor=False),
                          EngineConfig(max_batch=2, prefill_budget=16,
                                       decode_steps=decode_steps))
        eng.load(_mk_reqs(n=2, prompt=30, out=10), [])
        fin = eng.run()
        assert len(fin) == 2
        return eng, {r.rid: list(be.generated[r.rid]) for r in fin}

    eng1, ref = run(1)
    assert eng1.swap_bytes > 0, "pool too large: no eviction exercised"
    _, got = run(4)
    assert got == ref


def test_engine_decode_steps_telemetry_invariant():
    """Telemetry must never feed back into execution: streams and the
    step-by-step accounting are identical with the registry on and off,
    and per-token artifacts (token_times, TTFT) exist per micro-step."""
    _, _, off = _run_engine(4)
    eng, _, on = _run_engine(4, obs=MetricsRegistry())
    assert on == off
    for r in eng.finished:
        assert len(r.token_times) == r.true_output_len
        assert r.first_token_t is not None
        # micro-step clock advances strictly within a window
        assert all(b > a for a, b in zip(r.token_times, r.token_times[1:]))
