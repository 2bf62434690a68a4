"""PagedJaxBackend behind the Backend protocol: the ONE ServeEngine run
loop drives real JAX execution — chunked prefill, batched paged decode
(Pallas kernel, interpret mode), KV eviction/swap with byte-exact
restore, seeded sampling — single replica and 2-replica cluster."""

import numpy as np
import pytest

from repro.core.baselines import make_scheduler
from repro.core.service import ServiceModel
from repro.serving.backend import Sampler
from repro.serving.engine import EngineConfig, ServeEngine
from repro.serving.jax_backend import PagedJaxBackend
from repro.serving.metrics import summarize
from repro.serving.request import Request, SLOSpec


def _mk_reqs(n=2, prompt=30, out=10, kind="throughput", ttlt=1e6):
    return [Request(rid=i + 1, app="chatbot", arrival=0.0,
                    prompt_len=prompt, true_output_len=out,
                    slo=SLOSpec(kind, ttlt=ttlt))
            for i in range(n)]


def _run_tempo(num_blocks=4, seed=0):
    """2 requests × (30 prompt + 10 out) on a 4-block×16-token pool: both
    cross a page boundary mid-decode with the pool exhausted, forcing at
    least one eviction; prefill_budget=16 forces chunked prefill."""
    be = PagedJaxBackend(num_blocks=num_blocks, page=16, max_len=64,
                         seed=seed)
    eng = ServeEngine(be, make_scheduler("tempo", use_predictor=False),
                      EngineConfig(max_batch=2, prefill_budget=16))
    reqs = _mk_reqs()
    eng.load(reqs, [])
    fin = eng.run()
    return eng, be, fin


def test_engine_tempo_chunked_prefill_eviction_goodput_determinism():
    """The acceptance path: ServeEngine + Tempo on PagedJaxBackend with
    chunked prefill and ≥1 KV eviction produces non-zero goodput and
    per-token texts identical across two seeded runs."""
    eng, be, fin = _run_tempo()
    assert len(fin) == 2
    assert all(r.decoded == r.true_output_len for r in fin)
    assert eng.swap_bytes > 0                      # ≥1 eviction happened
    assert all(len(be.generated[r.rid]) == r.true_output_len for r in fin)
    s = summarize("tempo@jax", fin, ServiceModel(), eng.now)
    assert s.goodput_frac > 0
    # second seeded run: byte-identical token streams
    eng2, be2, fin2 = _run_tempo()
    assert {r.rid: be2.generated[r.rid] for r in fin2} == \
           {r.rid: be.generated[r.rid] for r in fin}


def test_swap_roundtrip_preserves_texts():
    """Texts under a tiny pool (evictions + host round-trips) must equal
    texts under a big pool (no evictions): swap must restore KV exactly."""
    _, be_small, fin_s = _run_tempo(num_blocks=4)
    _, be_big, fin_b = _run_tempo(num_blocks=32)
    small = {r.rid: be_small.generated[r.rid] for r in fin_s}
    big = {r.rid: be_big.generated[r.rid] for r in fin_b}
    assert small == big


def test_texts_independent_of_batch_composition():
    """Sampling keys on (seed, rid, position) and paged attention isolates
    sequences, so token streams must not depend on which scheduler (and
    hence batch composition) served them — even at temperature > 0."""
    texts = {}
    for name in ("vllm", "tempo"):
        be = PagedJaxBackend(num_blocks=16, page=16, max_len=64, seed=0,
                             temperature=0.8, top_k=20)
        eng = ServeEngine(be, make_scheduler(name, use_predictor=False)
                          if name == "tempo" else make_scheduler(name),
                          EngineConfig(max_batch=2, prefill_budget=16))
        reqs = _mk_reqs(n=3, prompt=20, out=8)
        eng.load(reqs, [])
        fin = eng.run()
        assert len(fin) == 3
        texts[name] = {r.rid: list(be.generated[r.rid]) for r in fin}
    assert texts["vllm"] == texts["tempo"]


def test_sampler_seeded_topk():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=256)
    s = Sampler(temperature=0.7, top_k=10, seed=42)
    a = [s.sample(logits, rid=3, pos=p) for p in range(16)]
    b = [s.sample(logits, rid=3, pos=p) for p in range(16)]
    assert a == b                                  # fixed seed -> fixed draw
    assert len(set(a)) > 1                         # actually stochastic
    top10 = set(np.argsort(logits)[-10:])
    assert set(a) <= top10                         # top-k respected
    greedy = Sampler(temperature=0.0, seed=42)
    assert greedy.sample(logits, 3, 0) == int(np.argmax(logits))


def test_latency_stream_first_token_via_decode():
    """Latency requests stream through the same decode path: TTFT/TBT are
    recorded from real step times."""
    be = PagedJaxBackend(num_blocks=16, page=16, max_len=64, seed=0)
    eng = ServeEngine(be, make_scheduler("tempo", use_predictor=False),
                      EngineConfig(max_batch=4, prefill_budget=32))
    reqs = _mk_reqs(n=3, prompt=12, out=6, kind="latency")
    for r in reqs:
        r.slo = SLOSpec("latency", ttft=1e6, tbt=1e6)
    eng.load(reqs, [])
    fin = eng.run()
    assert len(fin) == 3
    for r in fin:
        assert r.ttft() is not None and r.ttft() > 0
        assert len(r.token_times) == r.true_output_len


def test_backend_rejects_oversized_request():
    be = PagedJaxBackend(num_blocks=8, page=16, max_len=32, seed=0)
    eng = ServeEngine(be, make_scheduler("sarathi"),
                      EngineConfig(max_batch=2, prefill_budget=64))
    eng.load(_mk_reqs(n=1, prompt=30, out=10), [])
    with pytest.raises(ValueError, match="max_len"):
        eng.run()


def test_backend_rejects_non_attention_arch():
    with pytest.raises(ValueError, match="paged serving"):
        PagedJaxBackend(arch="xlstm-1.3b")


def _run_multiturn(prefix_cache):
    """Multi-turn chat on real JAX decoding: follow-up turns adopt the
    previous turn's prompt pages (full pages + the prompt-boundary COW
    tail) out of the prefix cache."""
    from repro.serving.workload import WorkloadGen, WorkloadSpec
    spec = WorkloadSpec(scenario="multiturn", rate=0.5, duration=8.0,
                        seed=0, turns=(2, 3), think_time=40.0,
                        system_prompt_len=8, shared_system_frac=1.0,
                        prompt_cap=8, output_cap=4, slo_scale=50.0)
    gen = WorkloadGen(spec)
    be = PagedJaxBackend(num_blocks=64, page=16, max_len=128, seed=0)
    eng = ServeEngine(be, make_scheduler("sarathi"),
                      EngineConfig(max_batch=4, prefill_budget=32,
                                   prefix_cache=prefix_cache),
                      workload=gen)
    singles, dags = gen.generate()
    eng.load(singles, dags)
    fin = eng.run()
    return eng, be, fin


def test_prefix_cache_token_streams_identical_on_vs_off():
    """Acceptance: cached prefixes (adopted donor pages + COW-forked
    tails) must decode the EXACT token streams the cache-off run computes
    from scratch — shared pages never leak a mutation."""
    eon, bon, fon = _run_multiturn(True)
    eoff, boff, foff = _run_multiturn(False)
    assert {r.rid for r in fon} == {r.rid for r in foff}
    on = {r.rid: list(bon.generated[r.rid]) for r in fon}
    off = {r.rid: list(boff.generated[r.rid]) for r in foff}
    assert on == off                               # byte-identical
    # the cache actually did something: hits, COW forks, fewer prefills
    assert eon.prefix_hits > 0
    assert eon.cow_forks > 0
    assert eon.prefill_computed < eoff.prefill_computed
    assert eoff.prefix_hits == 0
    eon.kv.check_invariants()


def test_cluster_two_replicas_real_execution():
    """2-replica ClusterEngine over PagedJaxBackend: the co-simulation
    routes real work, both replicas decode, fleet goodput is non-zero, and
    two seeded runs emit identical per-token texts."""
    from repro.cluster.engine import ClusterEngine
    from repro.cluster.router import make_router

    def run_once():
        backends = {}

        def factory(rid):
            backends[rid] = PagedJaxBackend(num_blocks=16, page=16,
                                            max_len=64, seed=0)
            return ServeEngine(backends[rid],
                               make_scheduler("tempo", use_predictor=False),
                               EngineConfig(max_batch=2, prefill_budget=16))

        cluster = ClusterEngine(factory, make_router("round-robin"),
                                n_replicas=2)
        reqs = _mk_reqs(n=4, prompt=20, out=6)
        for i, r in enumerate(reqs):
            r.arrival = 0.05 * i
        stream = [(r.arrival, "r", r) for r in reqs]
        fin = cluster.run(iter(stream))
        texts = {}
        for rid, rs in fin.items():
            for r in rs:
                texts[r.rid] = list(backends[rid].generated[r.rid])
        return fin, texts

    fin, texts = run_once()
    all_fin = [r for rs in fin.values() for r in rs]
    assert len(all_fin) == 4
    assert all(len(rs) > 0 for rs in fin.values())   # both replicas served
    s = summarize("cluster@jax", all_fin, ServiceModel(), 10.0)
    assert s.goodput_frac > 0
    _, texts2 = run_once()
    assert texts == texts2


def test_backend_builds_a_given_config_and_resolves_interpret():
    """An arch name serves its reduced CPU variant; a ModelConfig (for
    example the published widths) is served as given.  ``interpret``
    comes from the platform, never from the caller."""
    import dataclasses

    import jax

    from repro.configs.archs import reduced_config
    by_name = PagedJaxBackend("tinyllama-1.1b", num_blocks=2, page=16,
                              max_len=32)
    assert by_name.cfg == reduced_config("tinyllama-1.1b")
    cfg = dataclasses.replace(by_name.cfg, d_model=32, dtype="bfloat16")
    be = PagedJaxBackend(cfg, num_blocks=2, page=16, max_len=32)
    assert be.cfg is cfg
    assert be.params["embed"].shape == (cfg.vocab_size, 32)
    assert be.params["embed"].dtype == jax.numpy.bfloat16
    assert be.interpret == (jax.default_backend() != "tpu")


def test_compiles_counter_counts_each_new_shape_once():
    """``jax_compiles_total`` counts the XLA compiles a backend's
    dispatches trigger: a decode width dispatched for the first time is
    one compile, the same width again none, another width one more."""
    from repro.obs import MetricsRegistry
    obs = MetricsRegistry()
    be = PagedJaxBackend(num_blocks=8, page=16, max_len=64, seed=0)
    be.attach_obs(obs)
    reqs = _mk_reqs(n=2, prompt=8, out=4)
    for r in reqs:
        be.prefill_chunk(r, 0, r.prompt_len, [reqs.index(r)])
    be.step_time(16, [])

    def decode(rs):
        n0 = obs.value_of("jax_compiles_total")
        be.begin_step()
        be.decode_batch(rs, [[reqs.index(r)] for r in rs])
        be.step_time(0, [8] * len(rs))
        return obs.value_of("jax_compiles_total") - n0

    assert obs.value_of("jax_compiles_total") == 1     # the prefill bucket
    assert decode(reqs[:1]) == 1
    assert decode(reqs[:1]) == 0
    assert decode(reqs) == 1
    assert decode(reqs) == 0
