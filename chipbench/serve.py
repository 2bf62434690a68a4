"""Set-up and the timed serving loop of one cell.

The system under test is the program's normal serving path:
``ServeEngine(PagedJaxBackend(...), make_scheduler("gmg", ...),
EngineConfig())`` with every engine setting at its default.  The benchmark
owns the clock: it enqueues each request when it is due on the wall clock,
sets the engine's ``now`` from the wall clock before every step (never
backwards), and stamps each token when the ``step_once`` that produced it
returns (the backend has synced the device by then).  The engine's own
clock feeds no number.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import time
from typing import Dict, List, Optional
from unittest import mock

import jax
import numpy as np

from chipbench import traffic as tr

# Device memory kept out of the page pool beyond what the compiled steps
# report: the allocator's fragmentation, the sampler's and the host
# transfers' small buffers.
POOL_MARGIN_BYTES = 1 << 30
PAGE = 16                 # tokens per KV page (the backend's default)


def _bucket(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# the program, as the benchmark builds it
# ---------------------------------------------------------------------------
def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(**cfg["serve_as"])


def _backend(cfg: dict, weights, num_blocks: int, max_len: int, seed: int):
    """The program's backend, handed the benchmark's weights: the backend
    builds its own in its constructor, so ``Model.init`` is replaced for
    that one call and no second copy is ever made."""
    from repro.models.model import Model
    from repro.serving.jax_backend import PagedJaxBackend
    with mock.patch.object(Model, "init", lambda self, key: weights):
        return PagedJaxBackend(model_config(cfg), num_blocks=num_blocks,
                               page=PAGE, max_len=max_len,
                               seed=seed & 0x7FFFFFFF)


def _engine(backend):
    from repro.core.baselines import make_scheduler
    from repro.core.service import ServiceModel
    from repro.serving.engine import EngineConfig, ServeEngine
    sched = make_scheduler("gmg", service=ServiceModel())
    return ServeEngine(backend, sched, EngineConfig())


def _request(rid: int, a: tr.Arrival, due: float, tokens=None):
    from repro.serving.request import Request, SLOSpec
    app = {"latency": "chatbot", "throughput": "code",
           "collective": "math", "none": "batch"}[a.kind]
    kind = "throughput" if a.kind == "collective" else a.kind
    r = Request(rid=rid, app=app, arrival=due, prompt_len=a.prompt_len,
                true_output_len=a.output_len,
                slo=SLOSpec(kind, ttft=a.slo.ttft, tbt=a.slo.tbt,
                            ttlt=a.slo.ttlt))
    r.meta["hint"] = a.hint
    if tokens is not None:
        r.meta["prompt_tokens"] = tokens
    return r


def engine_defaults():
    from repro.serving.engine import EngineConfig
    c = EngineConfig()
    return c.max_batch, c.prefill_budget


# ---------------------------------------------------------------------------
# shapes the cell's traffic can reach
# ---------------------------------------------------------------------------
def prefill_groups(max_prompt: int, budget: int, max_batch: int):
    """(bucket C, lanes L) of every batched prefill dispatch a step can
    issue: chunks of at most ``min(max_prompt, budget)`` tokens, padded to
    power-of-two buckets from 8; same-bucket chunks share a dispatch whose
    lane count is padded to a power of two from 2 (L = 1: the single-chunk
    program).  A chunk in bucket C > 8 holds more than C/2 tokens, so at
    most budget // (C/2 + 1) of them fit one step's budget."""
    out = []
    cmax = _bucket(min(max_prompt, budget), 8)
    C = 8
    while C <= cmax:
        k = max_batch if C == 8 else min(max_batch, budget // (C // 2 + 1))
        out.append((C, 1))
        L = 2
        while L // 2 < k:
            out.append((C, L))
            L *= 2
        C *= 2
    return out


def decode_widths(max_batch: int):
    return [1 << i for i in range(_bucket(max_batch, 1).bit_length())]


# ---------------------------------------------------------------------------
# page pool sized from the compiled steps
# ---------------------------------------------------------------------------
PROBE_PAGES = (256, 512)
POOL_ROUND = 64           # pages; a pool size that moves recompiles every step


def _spec(x):
    if isinstance(x, (jax.Array, np.ndarray, np.generic)):
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype)
    return x


class _Programs:
    """Stands in for ``jax.jit`` while a probe backend is built and driven
    through its protocol: each program the protocol dispatches is recorded
    with the shapes it was called with, so that it can be compiled for the
    compiler's memory analysis.  Calls made while tracing another program
    (their arguments are tracers) are part of that program and not
    recorded."""

    def __init__(self):
        self.jit = jax.jit
        self.seen: Dict = {}

    def __call__(self, fun, **kw):
        prog = self.jit(fun, **kw)

        def call(*a, **k):
            leaves = jax.tree.leaves((a, k))
            if not any(isinstance(x, jax.core.Tracer) for x in leaves):
                specs = jax.tree.map(_spec, (a, k))
                key = (id(prog), str(jax.tree.map(
                    lambda x: (x.shape, str(x.dtype))
                    if isinstance(x, jax.ShapeDtypeStruct) else x, specs)))
                self.seen.setdefault(key, (prog, specs))
            return prog(*a, **k)

        return call

    def extra_bytes(self) -> List[int]:
        """Device bytes each recorded program adds to what is live while it
        runs: outputs not aliased to inputs, plus temporaries."""
        out = []
        for prog, (a, k) in self.seen.values():
            m = prog.lower(*a, **k).compile().memory_analysis()
            out.append(m.output_size_in_bytes - m.alias_size_in_bytes
                       + m.temp_size_in_bytes)
        return out


def probe_groups(groups):
    """The prefill dispatches whose temporaries can be largest: the largest
    chunk alone, the most tokens, and the most lanes in the smallest
    bucket."""
    return sorted({max(groups), max(groups, key=lambda g: g[0] * g[1]),
                   max(groups, key=lambda g: g[1])})


def pool_pages(cfg, weights, max_len, groups, max_batch, device,
               seed) -> Dict:
    """The largest pool the cell's compiled steps leave room for.  What is
    live (the weights, measured) plus the pool plus what each step adds
    (its outputs and temporaries, from the compiler's memory analysis of
    the programs that a probe backend's protocol dispatches) must stay
    under ``bytes_limit - POOL_MARGIN_BYTES``.  A step's additions are
    linear in the pool, so two probe sizes give their cost per page."""
    stats = device.memory_stats() or {}
    limit = stats.get("bytes_limit")
    if not limit:
        raise SystemExit(f"{device.device_kind} reports no bytes_limit")
    live = stats["bytes_in_use"]
    probe = probe_groups(groups)
    B = _bucket(max_batch, 1)
    n, page_bytes = {}, 0
    for p in PROBE_PAGES:
        progs = _Programs()
        with mock.patch.object(jax, "jit", progs):
            be = _backend(cfg, weights, p, max_len, seed)
            warm_up(be, probe, [B])
        page_bytes = sum(x.size * x.dtype.itemsize
                         for x in jax.tree.leaves(be.pages)) / (p + 1)
        be.pages = be.params = None
        del be
        gc.collect()
        n[p] = progs.extra_bytes()
        if len(n[p]) != len(n[PROBE_PAGES[0]]):
            raise SystemExit(f"pool probes dispatched {len(n[p])} and "
                             f"{len(n[PROBE_PAGES[0]])} programs")
    p0, p1 = PROBE_PAGES
    slope = [(b - a) / (p1 - p0) for a, b in zip(n[p0], n[p1])]
    room = limit - POOL_MARGIN_BYTES - live
    # live + (pages + 1 scrap) * page_bytes + a + (pages - p0) * slope
    pages = min(int((room - page_bytes - a + p0 * s) // (page_bytes + s))
                for a, s in zip(n[p0], slope))
    pages = pages // POOL_ROUND * POOL_ROUND
    if pages < PROBE_PAGES[0]:
        raise SystemExit(f"room for {pages} pages only")
    return dict(pages=pages, limit=int(limit), live=int(live),
                page_bytes=int(page_bytes), probed=probe,
                programs=len(n[p0]), extra_per_page=slope,
                extra_at_probe=n[p0])


# ---------------------------------------------------------------------------
# warm-up through the backend's own protocol
# ---------------------------------------------------------------------------
def warm_up(be, groups, widths) -> None:
    """Run every prefill dispatch shape and decode width the cell can reach
    once, on the scrap page (an empty block table maps every slot there),
    then forget the warm-up's requests."""
    from repro.serving.request import Request, SLOSpec
    nl = max(max(L for _, L in groups), max(widths))
    for C, L in groups:
        for i in range(L):
            r = Request(rid=-(i + 1), app="warm", arrival=0.0, prompt_len=C,
                        true_output_len=1, slo=SLOSpec("none"))
            r.meta["prompt_tokens"] = np.zeros(C, np.int32)
            be.prefill_chunk(r, 0, C, [])
        be.begin_step()
        be.step_time(C * L, [])
        be.reset_run_state()
    reqs = [Request(rid=-(i + 1), app="warm", arrival=0.0, prompt_len=8,
                    true_output_len=2, slo=SLOSpec("none"))
            for i in range(nl)]
    for r in reqs:
        r.meta["prompt_tokens"] = np.zeros(8, np.int32)
    for B in widths:
        be.begin_step()
        be.decode_batch(reqs[:B], [[] for _ in range(B)])
        be.step_time(0, [8] * B)
        be.reset_run_state()


# ---------------------------------------------------------------------------
# the timed loop
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Step:
    t0: float                # wall s, from the start of the pre-roll
    t1: float
    prefill_tokens: int
    decode_seqs: int
    decode_ctx: int
    pool_used: float         # pages in use / pool, after the step
    chunks: List             # (start, tokens) of each prefill chunk


class _Annotated:
    """Wraps bound methods of the instances the harness built in
    ``jax.profiler.TraceAnnotation`` spans (traced runs only)."""

    def __init__(self):
        self._undo = []

    def wrap(self, obj, name: str, label: str):
        fn = getattr(obj, name)

        def call(*a, **k):
            with jax.profiler.TraceAnnotation(label):
                return fn(*a, **k)

        setattr(obj, name, call)
        self._undo.append((obj, name))

    def undo(self):
        for obj, name in self._undo:
            with contextlib.suppress(AttributeError):
                delattr(obj, name)


def annotate(eng) -> _Annotated:
    a = _Annotated()
    a.wrap(eng, "step_once", "engine.step_once")
    a.wrap(eng.sched, "schedule", "sched.schedule")
    be = eng.backend
    for m in ("prefill_chunk", "decode_batch", "decode_batch_n",
              "step_time", "kv_swap_out", "kv_swap_in", "kv_copy_page"):
        a.wrap(be, m, f"backend.{m}")
    return a


def serve(be, arrs: List[tr.Arrival], tokens, warm, preroll: float,
          seconds: float, *, trace_dir: Optional[str] = None) -> Dict:
    """Serve ``arrs`` open-loop through a fresh engine on ``be``; measure
    the window [preroll, preroll + seconds] of the wall clock."""
    from repro.serving.request import ReqState
    finished = ReqState.FINISHED
    eng = _engine(be)
    pred = getattr(eng.sched, "predictor", None)
    if pred is not None:
        pred.warm_start([_request(-(i + 1), tr.Arrival(0.0, *w), 0.0)
                         for i, w in enumerate(warm)])
    w0, w1 = preroll, preroll + seconds
    recs: Dict[int, dict] = {}
    live: Dict[int, object] = {}
    steps: List[Step] = []
    late: List[float] = []
    compiles = _CompileCount()
    ann = annotate(eng) if trace_dir else None
    traced = None
    i, n = 0, len(arrs)
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if trace_dir and traced is None and now >= w0:
            jax.profiler.start_trace(trace_dir, profiler_options=_OPTS)
            traced = [time.perf_counter() - t0, None]
        if now >= w1:
            break
        if now >= w0 and compiles.armed is False:
            compiles.arm()
        while i < n and arrs[i].due <= now:
            a = arrs[i]
            rid = i + 1
            r = _request(rid, a, a.due, tokens[i])
            eng.enqueue("r", r)
            live[rid] = r
            recs[rid] = dict(kind=a.kind, due=a.due, output_len=a.output_len,
                             prompt_len=a.prompt_len,
                             ttft_limit=a.slo.ttft, gap_limit=a.slo.tbt,
                             ttlt_limit=a.slo.ttlt, first_prefill=None,
                             deliveries=[], token_times=[], finish=None,
                             shed=False, decoded=0)
            late.append(now - a.due)
            i += 1
        if not live:
            nxt = arrs[i].due if i < n else w1
            if trace_dir and traced is None:
                nxt = min(nxt, w0)
            with _span(ann, "harness.wait"):
                time.sleep(max(0.0, min(nxt, w1) - now))
            continue
        eng.now = max(eng.now, now)
        nlog = len(eng.step_log)
        before = {rid: r.prefilled for rid, r in live.items()}
        eng.step_once()
        t1 = time.perf_counter() - t0
        chunks = []
        done = []
        for rid, r in live.items():
            rec = recs[rid]
            if r.prefilled > before[rid]:
                chunks.append((before[rid], r.prefilled - before[rid]))
                if rec["first_prefill"] is None:
                    rec["first_prefill"] = now
            if r.decoded > rec["decoded"]:
                k = r.decoded - rec["decoded"]
                rec["deliveries"].append((t1, k))
                rec["token_times"].extend([t1] * k)
                rec["decoded"] = r.decoded
            if r.finish_t is not None:
                rec["finish"] = t1
                done.append(rid)
            elif r.state == finished:          # shed by the scheduler
                rec["shed"] = True
                done.append(rid)
        for rid in done:
            del live[rid]
        if len(eng.step_log) > nlog:
            _, pf, nd, ctx = eng.step_log[-1]
            steps.append(Step(now, t1, pf, nd, ctx,
                              eng.kv.used_blocks / eng.kv.num_blocks,
                              chunks))
    if traced is not None:
        traced[1] = time.perf_counter() - t0
        jax.profiler.stop_trace()
        ann.undo()
    compiles.close()
    return dict(records=recs, steps=steps, window=(w0, w1), late=late,
                sent=i, compiles_in_window=compiles.count,
                traced=traced, engine=eng,
                generated={rid: list(be.generated.get(rid, ()))
                           for rid, rec in recs.items()
                           if rec["finish"] is not None})


@contextlib.contextmanager
def _span(ann, label):
    if ann is None:
        yield
    else:
        with jax.profiler.TraceAnnotation(label):
            yield


class _CompileCount:
    """Counts XLA compiles and persistent-cache loads once armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def arm(self):
        self.armed = True

    def close(self):
        self.armed = None

    def _on(self, event, duration, **_):
        if self.armed and event in self.EVENTS:
            self.count += 1


_OPTS = jax.profiler.ProfileOptions()
_OPTS.python_tracer_level = 0       # the harness's own spans are enough
_OPTS.enable_hlo_proto = False


def set_compile_cache(root: str) -> str:
    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
