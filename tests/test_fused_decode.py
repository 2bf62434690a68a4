"""Fused multi-step decode (DESIGN.md §10).

Three layers under test:
  1. the fused append+attend Pallas kernel vs the two-dispatch reference
     (``paged_kv_append_batch`` + ``paged_attention``) — output AND page
     write-back parity in interpret mode, property-tested over batch
     width, context length, page and block edges and retired lanes, in
     both lowerings; and a pool poisoned outside the live pages, which
     must never reach the output;
  2. ``decode_batch_n``: n micro-steps in one ``lax.scan`` dispatch must
     emit byte-identical token streams to n single-step dispatches — at
     temperature 0 and seeded temperature>0, including lanes that retire
     mid-scan and KV that swaps out/in across a multi-step window;
  3. the engine fast path: runs with ``decode_steps`` n∈{2,4,8} must
     finish the same requests with the same streams (and the same
     per-token SLO accounting shape) as n=1, telemetry on or off.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.baselines import make_scheduler
from repro.kernels.paged_attention import (_pages_per_block,
                                           fused_decode_attention,
                                           paged_attention,
                                           paged_kv_append_batch)
from repro.obs import MetricsRegistry
from repro.serving.engine import EngineConfig, ServeEngine
from repro.serving.jax_backend import PagedJaxBackend
from repro.serving.request import Request, SLOSpec


# ---------------------------------------------------------------------------
# 1. kernel parity: fused vs two-dispatch reference
# ---------------------------------------------------------------------------
def _pools(rng, P, page, KV, D):
    return (rng.normal(size=(P, page, KV, D)).astype(np.float32),
            rng.normal(size=(P, page, KV, D)).astype(np.float32))


def _lane_tables(rng, pos, n_max, page, P, retired=()):
    """Disjoint tables over pages 0..P-2 (a permutation, so index bugs
    show) for lanes decoding at ``pos``: live entries cover
    0..pos // page, the rest is scrap (P-1), as the backend pads them;
    ``retired`` lanes carry all-scrap tables."""
    B = len(pos)
    perm = rng.permutation(P - 1)[:B * n_max].reshape(B, n_max)
    tables = np.full((B, n_max), P - 1, np.int32)
    for b, p in enumerate(pos):
        if b not in retired:
            tables[b, :p // page + 1] = perm[b, :p // page + 1]
    return tables


def _check_two_dispatch(rng, tables, pos, page, KV, G, D, retired=()):
    """Fused kernel vs ``paged_kv_append_batch`` + ``paged_attention``:
    outputs of live lanes within 1e-5 (a retired lane's output is garbage
    the caller discards), page write-backs equal outside the scrap page."""
    B, n_max = tables.shape
    H, P = KV * G, B * n_max + 1
    k_pages, v_pages = _pools(rng, P, page, KV, D)
    q = rng.normal(size=(B, H, D)).astype(np.float32)
    k_new = rng.normal(size=(B, KV, D)).astype(np.float32)
    v_new = rng.normal(size=(B, KV, D)).astype(np.float32)
    pos = np.asarray(pos, np.int32)

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

    live = [b for b in range(B) if b not in retired]
    np.testing.assert_allclose(np.asarray(o_fus)[live],
                               np.asarray(o_ref)[live],
                               rtol=1e-5, atol=1e-5)
    # page write-back parity everywhere but the scrap page (padded and
    # retired lanes append there)
    np.testing.assert_array_equal(np.asarray(kf)[:-1], np.asarray(kp)[:-1])
    np.testing.assert_array_equal(np.asarray(vf)[:-1], np.asarray(vp)[:-1])


@settings(max_examples=12, deadline=None)
@given(data=st.data(), B=st.integers(1, 3), n_max=st.integers(1, 7),
       page=st.sampled_from([4, 16, 64, 128]), KV=st.sampled_from([1, 2]),
       G=st.sampled_from([1, 2]), D=st.sampled_from([4, 128]),
       seed=st.integers(0, 10**6))
def test_fused_kernel_matches_two_dispatch(data, B, n_max, page, KV, G, D,
                                           seed):
    """Property test over batch width, table width, page and head sizes
    (both lowerings: D=128 DMAs the pool, D=4 takes page windows) and
    write positions biased to page and block edges; with B > 1 a lane may
    be retired (all-scrap table)."""
    rng = np.random.default_rng(seed)
    bk = page * _pages_per_block(page, n_max)
    top = n_max * page - 1
    edges = sorted({0, page - 1, page, bk - 1, bk, top} & set(range(top + 1)))
    pos = data.draw(st.lists(st.sampled_from(edges) | st.integers(0, top),
                             min_size=B, max_size=B))
    retired = ({data.draw(st.integers(0, B - 1))}
               if B > 1 and data.draw(st.booleans()) else set())
    tables = _lane_tables(rng, pos, n_max, page, B * n_max + 1, retired)
    _check_two_dispatch(rng, tables, pos, page, KV, G, D, retired)


def _edge_case(page):
    """(n_max, write positions) that put a lane on each edge of the block
    walk at this page size: a single live page (write on slot 0), a
    context ending exactly on a block edge (write on the block's last
    page), the write on the next block's first page, the table's last
    slot inside a partial last block (n_max not a multiple of the block,
    where blocks are more than one page), a context ending mid-block, and
    one more lane (retired in the parity test)."""
    ppb = _pages_per_block(page, 1 << 20)
    bk = ppb * page
    n_max = 2 * ppb - 1 if ppb > 1 else 3
    return n_max, [0, bk - 1, bk, n_max * page - 1, bk // 2 + 1, bk + 1]


_EDGE_PAGES = [4, 32, 128]


@pytest.mark.parametrize("D", [4, 128])
@pytest.mark.parametrize("page", _EDGE_PAGES)
def test_fused_kernel_block_edges(page, D):
    """The block walk's edges (``_edge_case``) against the two-dispatch
    reference, with the last lane retired (all-scrap table); D=128 DMAs
    the pool, D=4 takes page windows."""
    n_max, pos = _edge_case(page)
    rng = np.random.default_rng(D + page)
    B = len(pos)
    retired = {B - 1}
    tables = _lane_tables(rng, pos, n_max, page, B * n_max + 1, retired)
    _check_two_dispatch(rng, tables, pos, page, KV=2, G=2, D=D,
                        retired=retired)


@pytest.mark.parametrize("D", [4, 128])
@pytest.mark.parametrize("page", _EDGE_PAGES)
def test_fused_kernel_reads_only_live_pages(page, D):
    """Every pool page outside the lanes' live table ranges, the scrap
    page included, holds NaN: the outputs must be finite and equal a
    dense reference over the live tokens alone (a kernel that reads a
    dead page leaks 0 * NaN into p·V), and the pool must come back
    unchanged but for the one appended row per lane."""
    n_max, pos = _edge_case(page)
    pos = np.asarray(pos, np.int32)
    B, KV, G = len(pos), 2, 3
    H, P = KV * G, len(pos) * n_max + 1
    rng = np.random.default_rng(D * page)
    tables = _lane_tables(rng, pos, n_max, page, P)
    k_pages, v_pages = _pools(rng, P, page, KV, D)
    live = np.zeros(P, bool)
    for b, p in enumerate(pos):
        live[tables[b, :p // page + 1]] = True
    k_pages[~live] = np.nan
    v_pages[~live] = np.nan
    q = rng.normal(size=(B, H, D)).astype(np.float32)
    k_new = rng.normal(size=(B, KV, D)).astype(np.float32)
    v_new = rng.normal(size=(B, KV, D)).astype(np.float32)

    out, kf, vf = fused_decode_attention(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(tables),
        jnp.asarray(pos), interpret=True)

    k_want, v_want = k_pages.copy(), v_pages.copy()
    for b, p in enumerate(pos):
        k_want[tables[b, p // page], p % page] = k_new[b]
        v_want[tables[b, p // page], p % page] = v_new[b]
    np.testing.assert_array_equal(np.asarray(kf), k_want)
    np.testing.assert_array_equal(np.asarray(vf), v_want)

    out = np.asarray(out)
    assert np.isfinite(out).all()
    for b, p in enumerate(pos):
        keys = k_want[tables[b, :p // page + 1]].reshape(-1, KV, D)[:p + 1]
        vals = v_want[tables[b, :p // page + 1]].reshape(-1, KV, D)[:p + 1]
        s = jnp.einsum("kgd,tkd->kgt", q[b].reshape(KV, G, D),
                       keys) * D ** -0.5
        want = jnp.einsum("kgt,tkd->kgd", jax.nn.softmax(s, axis=-1), vals)
        np.testing.assert_allclose(out[b], np.asarray(want).reshape(H, D),
                                   rtol=1e-5, atol=1e-5)


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
