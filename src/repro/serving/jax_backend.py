"""PagedJaxBackend: real JAX execution behind the Backend protocol.

A model genuinely prefills and decodes on device through the
unified Model API (``prefill_paged`` / ``decode_paged``) against a single
device-resident paged KV cache.  Block tables come from the engine's
``BlockManager`` — the same allocator that models KV pressure for the
simulator — so *one* run loop (``ServeEngine._execute``), every scheduler,
eviction/swap, and the whole cluster stack work identically over simulated
and real execution.

Geometry: the device pool holds ``num_blocks`` pages of ``page`` tokens
plus ONE scrap page (index ``num_blocks``) that absorbs the KV writes of
padded batch/chunk rows; the scrap page never appears in a live block
table, so padding can't corrupt resident sequences.  Chunks are padded to
power-of-two buckets and decode batches to power-of-two widths to bound
the number of XLA compiles (compile time lands in measured step time, like
a real replica's cold start).

Eviction fidelity: ``kv_swap_out`` copies the victim's pages to host
before the engine recycles its blocks; ``kv_swap_in`` writes them back
into the (new) blocks — so a preempted-and-resumed sequence decodes
byte-identical continuations.

Sampling is seeded temperature/top-k keyed per (rid, position) — token
streams are reproducible under a fixed seed regardless of batch
composition (greedy argmax at temperature 0).

Raw-speed decode pass (DESIGN.md §10): sampling runs ON DEVICE
(``Sampler.sample_device``), attention takes the fused append+attend
kernel (``fused_decode_attention``, one dispatch instead of two), and
``decode_batch_n`` runs up to n decode micro-steps inside one
``jax.lax.scan`` dispatch — the sampled token feeds back as the next
input, positions increment on device, finished lanes retire to the scrap
page via per-lane remaining-token masks, and the host syncs once per n
tokens.  ``decode_batch`` is ``decode_batch_n(n=1)``, so single- and
multi-step dispatch share one compiled body and token streams are
byte-identical across horizons at temperature 0.  Prefill chunks are
queued per step and flushed as batched dispatches (same-bucket chunks
share one ``lax.scan`` dispatch); the one host sync per step lives in
``step_time``.

Tensor parallelism (DESIGN.md §8): ``tp > 1`` executes every step under a
``shard_map`` over a 1-D ``('model',)`` mesh of ``tp`` devices.  Resident
weights shard Megatron-style per ``launch.sharding.paged_param_specs``
(attention projections on the head dim, MLP on d_ff, lm_head on vocab);
the page pool shards its KV-head dim (``paged_page_specs``), so the
Pallas kernels run unchanged on each shard's local heads and only the
wo / w_down partial sums are all-reduced.  When ``num_kv_heads % tp != 0``
the attention subsystem (weights + pool) falls back to replication and
only divisible subsystems shard.  With a sharded pool each device holds
``1/tp`` of every page, so the backend hosts ``num_blocks × tp`` pages at
the same per-device footprint — the engine's BlockManager sees the
mesh-wide aggregate pool.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.archs import reduced_config
from repro.configs.base import ModelConfig
from repro.kernels.ops import _default_interpret
from repro.launch.sharding import (paged_page_specs, paged_param_specs,
                                   paged_tp_plan, serving_tp_ctx)
from repro.models.model import build_model
from repro.obs import span
from repro.serving.backend import Backend, Sampler
from repro.serving.drafter import NgramDrafter


def _bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _named(name: str, fun, **kw):
    """``functools.partial(fun, **kw)`` under a stable name: JAX names a
    compiled program after its function (``jit_<name>``, which profiles
    show), and a bare partial compiles as ``jit__unknown``."""
    p = functools.partial(fun, **kw)
    p.__name__ = name
    return p


class _Compiles:
    """XLA compiles and persistent-cache loads in this process, counted by
    one listener on JAX's monitoring events, installed with the first
    backend; each backend books the ones its own dispatches trigger
    (``jax_compiles_total``)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")
    n = 0
    _installed = False

    @classmethod
    def install(cls) -> None:
        if not cls._installed:
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls._installed = True

    @classmethod
    def _on(cls, event: str, duration: float, **_) -> None:
        if event in cls.EVENTS:
            cls.n += 1


class PagedJaxBackend(Backend):
    supports_multi_step = True
    supports_spec_decode = True

    def __init__(self, arch: Union[str, ModelConfig] = "tinyllama-1.1b",
                 num_blocks: int = 64, page: int = 16, max_len: int = 128,
                 seed: int = 0, temperature: float = 0.0, top_k: int = 0,
                 overhead: float = 1e-4, tp: int = 1,
                 devices: Optional[Sequence] = None, fused: bool = True,
                 drafter=None):
        # an arch NAME serves its reduced CPU-sized variant; a ModelConfig
        # (e.g. ``get_config(name)``, the published widths) is served as is
        self.cfg = reduced_config(arch) if isinstance(arch, str) else arch
        self.tp = max(int(tp), 1)
        mla = any(m == "mla" for m, _ in
                  self.cfg.prefix_pattern + self.cfg.unit_pattern)
        if mla and self.tp > 1:
            raise ValueError(f"{self.cfg.name}: latent attention serves on "
                             "one device (tp=1); its pool has no head dim "
                             "to shard")
        self.plan = paged_tp_plan(self.cfg, self.tp)
        if self.tp > 1:
            devs = list(devices) if devices else jax.devices()
            if len(devs) < self.tp:
                raise ValueError(
                    f"tp={self.tp} needs {self.tp} devices, have "
                    f"{len(devs)} (set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N on CPU)")
            self.mesh = Mesh(np.array(devs[:self.tp]), ("model",))
            ctx = serving_tp_ctx(self.cfg, self.tp)
        else:
            self.mesh = None
            ctx = None
        self.model = build_model(self.cfg, ctx)
        if not self.model.supports_paged():
            raise ValueError(
                f"{arch}: paged serving needs an attention stack (GQA or "
                "latent) with rope/none positions (recurrent mixers have "
                "no paged state)")
        # speculative verification has a GQA kernel only
        self.supports_spec_decode = not mla
        # routed-pair counts the MoE programs returned, read at sync
        self._counts_pending: List = []
        self.page = page
        self.max_len = max_len
        self.n_max = -(-max_len // page)         # block-table width
        # a KV-head-sharded pool costs 1/tp of a page per device, so the
        # same per-device HBM budget hosts tp× the pages: the pool the
        # engine allocates from is the MESH-WIDE aggregate
        pool = num_blocks * (self.tp if self.plan["attn"] else 1)
        self.scrap = pool                        # pad rows write here
        self.overhead = overhead
        # Pallas kernels compile on a TPU and are interpreted elsewhere
        self.interpret = _default_interpret()
        self.fused = bool(fused)
        self.sampler = Sampler(temperature=temperature, top_k=top_k,
                               seed=seed)
        self.generated: Dict[int, List[int]] = {}
        self._prompts: Dict[int, np.ndarray] = {}
        self._host: Dict[int, object] = {}       # swapped-out page contents
        # queued prefill chunks for the current step; flushed as batched
        # dispatches before anything reads the pages (decode / swap / sync)
        self._pf_queue: List[tuple] = []
        # per-rid padded block tables (rebuilt only when the table changes)
        self._tab_cache: Dict[int, tuple] = {}
        # preallocated decode staging buffers per batch bucket
        self._staging: Dict[int, tuple] = {}
        self._decode_n_cache: Dict[int, object] = {}
        # speculative decoding (DESIGN.md §11): deterministic drafter +
        # lazily built jitted verify dispatch (shape buckets retrace inside)
        self.drafter = drafter if drafter is not None else NgramDrafter()
        self._verify_fn = None
        # dispatch accounting (decode_speed bench: dispatches per token)
        self.n_decode_dispatches = 0
        self.n_decode_tokens = 0
        self.n_prefill_dispatches = 0
        self._seed = seed
        self._t_acc = 0.0
        self._pages_step = 0
        self._page_shardings = None
        key = jax.random.PRNGKey(seed)
        # +1: the scrap page lives at the end of the pool, outside the
        # BlockManager's 0..pool-1 range
        if self.mesh is None:
            self.params = self.model.init(key)
            self.pages = self.model.init_paged_caches(pool + 1, page)
            self._prefill = jax.jit(_named(
                "prefill_paged", self.model.prefill_paged,
                interpret=self.interpret))
            self._prefill_many = jax.jit(self._prefill_many_impl)
            # one decode step returning logits (host sampling) — the
            # roofline profile and logit checks read it
            self._decode = jax.jit(_named(
                "decode_step", self.model.decode_paged,
                interpret=self.interpret, fused=self.fused))
        else:
            self._build_sharded_step_fns(key, pool + 1)

        # engine-facing geometry (BlockManager mirrors the device pool).
        # kv_shard_degree is the factor each PAGE is split by across the
        # mesh — the replicated-KV fallback keeps full pages per device,
        # so it stays 1 there even though tp > 1
        self.block_tokens = page
        self.num_blocks = pool
        self.kv_bytes = float(self.model.kv_bytes_per_token())
        self.kv_shard_degree = self.tp if self.plan["attn"] else 1
        self.attach_obs(self.obs)       # resolve no-op instruments
        _Compiles.install()

    def attach_obs(self, obs) -> None:
        """Bind the run's metrics registry and pre-resolve the backend's
        instruments (DESIGN.md §9).  The engine calls this at
        construction; until then the class-level no-op registry holds."""
        self.obs = obs
        self._m_pages = obs.counter(
            "jax_pages_touched_total",
            "block-table pages referenced by dispatches (decode: the "
            "fused kernel reads a lane's live ones per layer, no other)")
        self._m_compile = obs.counter(
            "jax_compiles_total",
            "XLA compiles and persistent-cache loads the dispatches "
            "triggered")
        self._m_routed = {ph: obs.counter(
            "jax_moe_routed_total",
            "(token, held expert) pairs the router sent to this device's "
            "experts, summed over MoE layers", phase=ph)
            for ph in ("prefill", "decode")}
        self._m_moe_tokens = {ph: obs.counter(
            "jax_moe_tokens_total",
            "tokens that passed the MoE layers (each counted once)",
            phase=ph) for ph in ("prefill", "decode")}

    def _build_sharded_step_fns(self, key, n_pages: int) -> None:
        """Resident-sharded weights and page pool, and jit(shard_map(...))
        wrappers around the paged entry points.

        Weights and pool are generated directly into their shardings, so
        neither ever lands whole on one device (a tp-way pool holds tp×
        one device's pages).  Every other operand (tokens, positions,
        block tables) is replicated.  ``check_vma=False``: the psums
        inside attention/MLP make the activations replicated again, which
        shard_map can't prove."""
        pspecs = paged_param_specs(self.cfg, self.tp,
                                   jax.eval_shape(self.model.init, key))
        gspecs = paged_page_specs(
            self.cfg, self.tp, self.model.paged_cache_specs(n_pages,
                                                            self.page))
        self._pspecs, self._gspecs = pspecs, gspecs
        sh = lambda tree: jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), tree,
            is_leaf=lambda x: isinstance(x, P))
        self._param_shardings = sh(pspecs)
        self._page_shardings = sh(gspecs)
        self.params = jax.jit(self.model.init,
                              out_shardings=self._param_shardings)(key)
        self.pages = jax.jit(
            functools.partial(self.model.init_paged_caches, n_pages,
                              self.page),
            out_shardings=self._page_shardings)()
        # MoE experts stay replicated under tp, so routed counts are too
        self._prefill = jax.jit(jax.shard_map(
            self.model.prefill_paged, mesh=self.mesh,
            in_specs=(pspecs, gspecs, P(), P(), P(), P()),
            out_specs=(gspecs, P()), check_vma=False))
        self._prefill_many = jax.jit(jax.shard_map(
            self._prefill_many_impl, mesh=self.mesh,
            in_specs=(pspecs, gspecs, P(), P(), P(), P()),
            out_specs=(gspecs, P()), check_vma=False))
        self._decode = jax.jit(jax.shard_map(
            _named("decode_step", self.model.decode_paged,
                   interpret=self.interpret, fused=self.fused),
            mesh=self.mesh,
            in_specs=(pspecs, gspecs, P(), P(), P()),
            out_specs=(P(), gspecs, P()), check_vma=False))

    def _commit_pages(self) -> None:
        """Re-pin the pool's sharding after a host-side page mutation
        (swap-in scatter / COW copy) — no-op at tp=1 or when the eager op
        already preserved the placement."""
        if self._page_shardings is not None:
            self.pages = jax.device_put(self.pages, self._page_shardings)

    # ------------------------------------------------------------------
    # fused multi-step decode (DESIGN.md §10)
    # ------------------------------------------------------------------
    def _scan_decode(self, params, pages, toks, pos, tabs, rem, rids, *,
                     n: int):
        """n decode micro-steps in ONE dispatch via ``lax.scan``.

        Carry: (pages, input tokens, write positions, remaining budget).
        Each micro-step masks retired lanes (rem == 0) onto the scrap page,
        runs the fused append+attend decode, samples on device keyed per
        (seed, rid, pos), feeds the token back as the next input, and
        increments positions for active lanes only.  The scan body compiles
        once per (B, n) bucket and is iterated — not unrolled — so every
        micro-step runs bit-identical numerics regardless of n; that is
        what makes single- and multi-step token streams byte-equal."""
        scrap_row = jnp.full((1, self.n_max), self.scrap, jnp.int32)

        def micro(carry, _):
            pages, toks, pos, rem = carry
            active = rem > 0
            tabs_eff = jnp.where(active[:, None], tabs, scrap_row)
            logits, pages, cnt = self.model.decode_paged(
                params, pages, toks, pos, tabs_eff,
                interpret=self.interpret, fused=self.fused)
            nxt = self.sampler.sample_device(logits, rids, pos)
            toks = jnp.where(active, nxt, toks[:, 0])[:, None]
            pos = pos + active.astype(pos.dtype)
            rem = rem - active.astype(rem.dtype)
            return (pages, toks, pos, rem), (nxt, active, cnt)

        (pages, _, _, _), (nxt, active, cnt) = jax.lax.scan(
            micro, (pages, toks, pos, rem), None, length=n)
        # (B, n) tokens and active flags; MoE: routed pairs summed over n
        return nxt.T, active.T, pages, jax.tree.map(lambda c: c.sum(0), cnt)

    def _decode_n_fn(self, n: int):
        """Jitted (and, under tp, shard_mapped) scan dispatch for a given
        static horizon n — cached per n; shape buckets retrace inside.
        Every horizon compiles as ``jit_decode_scan``."""
        fn = self._decode_n_cache.get(n)
        if fn is None:
            body = _named("decode_scan", self._scan_decode, n=n)
            if self.mesh is None:
                fn = jax.jit(body)
            else:
                fn = jax.jit(jax.shard_map(
                    body, mesh=self.mesh,
                    in_specs=(self._pspecs, self._gspecs,
                              P(), P(), P(), P(), P()),
                    out_specs=(P(), P(), self._gspecs, P()),
                    check_vma=False))
            self._decode_n_cache[n] = fn
        return fn

    def _prefill_many_impl(self, params, pages, toks, starts, tabs, ns):
        """Scan a batch of same-bucket prefill chunks through one dispatch.
        Chunks in a step target distinct requests (disjoint pages), so
        lane order is irrelevant; padded lanes carry n=0 + all-scrap
        tables, and their discarded activations never touch the pool."""
        def body(pages, xs):
            t, s, tab, n = xs
            return self.model.prefill_paged(params, pages, t, s, tab, n,
                                            interpret=self.interpret)

        pages, cnt = jax.lax.scan(body, pages, (toks, starts, tabs, ns))
        return pages, jax.tree.map(lambda c: c.sum(0), cnt)

    @contextlib.contextmanager
    def _launch(self):
        """``backend.launch`` around jitted calls (argument transfer and
        enqueue); the compiles they trigger count into
        ``jax_compiles_total``."""
        n0 = _Compiles.n
        with span("backend.launch"):
            yield
        self._m_compile.inc(_Compiles.n - n0)

    def _staging_bufs(self, B: int):
        bufs = self._staging.get(B)
        if bufs is None:
            bufs = (np.zeros((B, 1), np.int32),          # input tokens
                    np.zeros(B, np.int32),               # write positions
                    np.full((B, self.n_max), self.scrap, np.int32),
                    np.zeros(B, np.int32),               # remaining budget
                    np.zeros(B, np.int32))               # rids (sampling key)
            self._staging[B] = bufs
        return bufs

    # ------------------------------------------------------------------
    def prompt_ids(self, req) -> np.ndarray:
        """Prompt tokens: caller-supplied via req.meta['prompt_tokens'] or
        synthesized deterministically from (seed, rid)."""
        toks = self._prompts.get(req.rid)
        if toks is None:
            given = req.meta.get("prompt_tokens")
            if given is not None:
                toks = np.asarray(given, np.int32)
                if toks.shape[0] != req.prompt_len:
                    raise ValueError(
                        f"r{req.rid}: prompt_tokens length {toks.shape[0]} "
                        f"!= prompt_len {req.prompt_len}")
                if toks.size and int(toks.max()) >= self.cfg.vocab_size:
                    raise ValueError(
                        f"r{req.rid}: prompt token {int(toks.max())} out of "
                        f"vocab (vocab_size={self.cfg.vocab_size})")
            else:
                rng = np.random.default_rng(
                    (self._seed, req.rid & 0x7FFFFFFF))
                toks = rng.integers(0, self.cfg.vocab_size,
                                    size=req.prompt_len).astype(np.int32)
            self._prompts[req.rid] = toks
        return toks

    def _padded_table(self, rid: int, table: List[int]) -> np.ndarray:
        """Padded (n_max,) device block table for rid, cached until the
        table's contents change (append/COW fork/swap move the request to
        different pages — caught by list comparison, not by hooks)."""
        tl = list(table)
        ent = self._tab_cache.get(rid)
        if ent is not None and ent[0] == tl:
            return ent[1]
        t = np.full(self.n_max, self.scrap, np.int32)
        t[:len(tl)] = tl
        self._tab_cache[rid] = (tl, t)
        return t

    # ------------------------------------------------------------------
    # Backend protocol
    # ------------------------------------------------------------------
    def begin_step(self) -> None:
        self._t_acc = 0.0
        self._pages_step = 0

    def reset_run_state(self) -> None:
        """Forget per-request state so one backend instance can serve a
        fresh run.  Benchmarks reuse an instance across an untimed warmup
        pass and the timed pass to keep XLA compiles (which land in
        measured step time by design) out of the timed numbers.  Compiled
        dispatches, staging buffers, and page geometry survive; stale page
        CONTENT is invisible — the next run's prefills rewrite every
        position a ctx-masked read can reach."""
        self.generated.clear()
        self._prompts.clear()
        self._host.clear()
        self._pf_queue.clear()
        self._counts_pending.clear()
        self._tab_cache.clear()
        self.n_decode_dispatches = 0
        self.n_decode_tokens = 0
        self.n_prefill_dispatches = 0
        self._t_acc = 0.0
        self._pages_step = 0

    def prefill_chunk(self, req, start: int, n: int,
                      block_table: List[int]) -> None:
        if req.prompt_len + req.true_output_len > self.max_len:
            raise ValueError(
                f"r{req.rid}: {req.prompt_len}+{req.true_output_len} tokens "
                f"exceed max_len={self.max_len}; raise max_len or cap the "
                "workload (WorkloadSpec.prompt_cap/output_cap)")
        prompt = self.prompt_ids(req)
        C = _bucket(n)
        self._pages_step += len(block_table)
        toks = np.zeros(C, np.int32)
        toks[:n] = prompt[start:start + n]
        # queue only — same-step chunks batch into one dispatch, and the
        # step's single host sync happens in step_time, not per chunk
        self._pf_queue.append(
            (C, toks, start, self._padded_table(req.rid, block_table), n))
        self.generated.setdefault(req.rid, [])

    def _flush_prefill(self) -> None:
        """Dispatch all queued prefill chunks.  Chunks sharing a bucket C
        go through one ``_prefill_many`` scan (lane count padded to its
        own bucket); singletons keep the original single-chunk dispatch.
        No sync here — the device pipeline drains in step_time."""
        q = self._pf_queue
        if not q:
            return
        self._pf_queue = []
        t0 = time.perf_counter()
        with span("backend.stage"):
            groups: Dict[int, list] = {}
            for item in q:
                groups.setdefault(item[0], []).append(item)
            calls = []
            for C, items in groups.items():
                if len(items) == 1:
                    _, toks, start, tab, n = items[0]
                    calls.append((self._prefill, (
                        toks[None, :], np.int32(start), tab, np.int32(n))))
                    continue
                L = _bucket(len(items), lo=2)
                toksL = np.zeros((L, 1, C), np.int32)
                starts = np.zeros(L, np.int32)
                tabsL = np.full((L, self.n_max), self.scrap, np.int32)
                ns = np.zeros(L, np.int32)
                for i, (_, toks, start, tab, n) in enumerate(items):
                    toksL[i, 0] = toks
                    starts[i] = start
                    tabsL[i] = tab
                    ns[i] = n
                calls.append((self._prefill_many, (toksL, starts, tabsL, ns)))
        with self._launch():
            for fn, args in calls:
                self.pages, cnt = fn(self.params, self.pages,
                                     *(jnp.asarray(a) for a in args))
                if cnt is not None:
                    self._counts_pending.append(cnt)
        if cnt is not None:         # an MoE stack: every call counts
            self._m_moe_tokens["prefill"].inc(sum(item[4] for item in q))
        self.n_prefill_dispatches += len(calls)
        self._t_acc += time.perf_counter() - t0

    def decode_batch(self, reqs: List, tables: List[List[int]]) -> None:
        """One real decode step for every request in the batch.

        Convention: the input token is the request's last token (prompt
        tail for the first step), written at position prompt_len-1+decoded;
        re-writing the prompt tail's KV on the first step is idempotent, so
        prefill needs no logits head and every emitted token flows through
        this one path.  Delegates to ``decode_batch_n(n=1)`` — single- and
        multi-step dispatch share one compiled scan body, so streams are
        byte-identical across horizons."""
        if not reqs:
            return
        self.decode_batch_n(reqs, tables, 1)

    def decode_batch_n(self, reqs: List, tables: List[List[int]], n: int):
        """Up to n decode micro-steps per request in ONE device dispatch
        (DESIGN.md §10).  Lanes retire to the scrap page when their true
        remaining output runs out mid-scan; the host syncs once for the
        whole window.  Returns (tokens (B, n) i32, active (B, n) bool)."""
        if not reqs:
            return (np.zeros((0, n), np.int32), np.zeros((0, n), bool))
        self._flush_prefill()
        nr = len(reqs)
        B = _bucket(nr, lo=1)
        self._pages_step += sum(len(t) for t in tables) * n
        with span("backend.stage"):
            toks, pos, tabs, rem, rids = self._staging_bufs(B)
            toks[nr:] = 0
            pos[nr:] = 0
            tabs[nr:] = self.scrap
            rem[nr:] = 0
            rids[nr:] = 0
            for i, r in enumerate(reqs):
                gen = self.generated.setdefault(r.rid, [])
                prompt = self.prompt_ids(r)
                toks[i, 0] = gen[-1] if gen else prompt[-1]
                pos[i] = r.prompt_len - 1 + r.decoded
                tabs[i] = self._padded_table(r.rid, tables[i])
                rem[i] = max(0, min(n, r.true_output_len - r.decoded))
                rids[i] = r.rid & 0x7FFFFFFF
        t0 = time.perf_counter()
        with self._launch():
            tok_n, act_n, self.pages, cnt = self._decode_n_fn(n)(
                self.params, self.pages, jnp.asarray(toks),
                jnp.asarray(pos), jnp.asarray(tabs), jnp.asarray(rem),
                jnp.asarray(rids))
        with span("backend.wait"):
            tok_n = np.asarray(tok_n)       # ONE host sync per n tokens
            act_n = np.asarray(act_n)
            cnt = None if cnt is None else np.asarray(cnt)
        self._t_acc += time.perf_counter() - t0
        self.n_decode_dispatches += 1
        with span("backend.unpack"):
            self.n_decode_tokens += int(act_n[:nr].sum())
            if cnt is not None:
                self._m_routed["decode"].inc(int(cnt.sum()))
                self._m_moe_tokens["decode"].inc(int(act_n[:nr].sum()))
            for i, r in enumerate(reqs):
                gen = self.generated[r.rid]
                for s in range(n):
                    if act_n[i, s]:
                        gen.append(int(tok_n[i, s]))
        return tok_n[:nr], act_n[:nr]

    # ------------------------------------------------------------------
    # speculative decoding (DESIGN.md §11)
    # ------------------------------------------------------------------
    def _verify_impl(self, params, pages, toks, pos0, widths, tabs, rem,
                     rids):
        """One verify forward + on-device accept for a drafted window.

        toks (B, W): row 0 the last accepted token, rows 1.. the drafts;
        the model scores every window position against the paged pool in
        one dispatch (per-row causal masking inside the kernel) and the
        sampler keeps the leading run of drafts that EQUAL the target's
        own samples, plus one bonus token.  ``rem`` clamps emission to the
        lane's remaining output budget (belt-and-braces: the engine caps
        depth at rem-1 before drafting)."""
        logits, pages = self.model.verify_paged(
            params, pages, toks, pos0, widths, tabs,
            interpret=self.interpret)
        targets, emitted = self.sampler.verify_device(
            logits, toks, rids, pos0, widths)
        return targets, jnp.minimum(emitted, jnp.maximum(rem, 1)), pages

    def _get_verify_fn(self):
        fn = self._verify_fn
        if fn is None:
            if self.mesh is None:
                fn = jax.jit(self._verify_impl)
            else:
                fn = jax.jit(jax.shard_map(
                    self._verify_impl, mesh=self.mesh,
                    in_specs=(self._pspecs, self._gspecs,
                              P(), P(), P(), P(), P(), P()),
                    out_specs=(P(), P(), self._gspecs), check_vma=False))
            self._verify_fn = fn
        return fn

    def decode_verify_batch(self, reqs: List, tables: List[List[int]],
                            depths: List[int]):
        """Draft-then-verify step: propose up to depths[i] tokens per lane
        from its own prompt+generated history (``NgramDrafter`` — pure
        function of visible tokens), score every window position in ONE
        dispatch, keep the longest accepted prefix + bonus token.  Every
        emitted token is the target model's own (seed, rid, pos)-keyed
        sample, so streams are byte-identical to spec-off; rejected
        suffixes leave only stale ctx-masked KV behind (the engine rolls
        back page refs via ``BlockManager.truncate``).  Returns per-lane
        (emitted, accepted, proposed)."""
        if not reqs:
            return []
        self._flush_prefill()
        drafts = []
        with span("backend.stage"):
            for r, d in zip(reqs, depths):
                d = int(d)
                if d <= 0:
                    drafts.append([])
                    continue
                gen = self.generated.setdefault(r.rid, [])
                hist = list(self.prompt_ids(r)) + gen
                drafts.append(self.drafter.propose(hist, d)[:d])
        # Partition: a verify window costs its full width in compute (the
        # interpret-mode lowering chains W forwards; on TPU the multi-row
        # kernel still reads W× the queries), so lanes the drafter came up
        # dry on ride the plain decode scan instead of padding the window.
        # Sampling is (seed, rid, pos)-keyed, so splitting the batch
        # cannot change any lane's tokens.
        dr_ix = [i for i, d in enumerate(drafts) if d]
        pl_ix = [i for i, d in enumerate(drafts) if not d]
        out: List = [None] * len(reqs)
        if pl_ix:
            tok, act = self.decode_batch_n(
                [reqs[i] for i in pl_ix], [tables[i] for i in pl_ix], 1)
            for j, i in enumerate(pl_ix):
                out[i] = (int(act[j, 0]), 0, 0)
        if not dr_ix:
            return out
        nr = len(dr_ix)
        B = _bucket(nr, lo=1)
        # width is EXACT, not pow2-bucketed: every extra column is a whole
        # extra forward pass in the window, far dearer than one retrace
        # per distinct draft depth (the depth policy grants few values)
        W = 1 + max(len(drafts[i]) for i in dr_ix)
        self._pages_step += sum(len(tables[i]) for i in dr_ix)
        with span("backend.stage"):
            toks = np.zeros((B, W), np.int32)
            pos0 = np.zeros(B, np.int32)
            widths = np.zeros(B, np.int32)   # pad lanes: width 0, all-scrap
            tabs = np.full((B, self.n_max), self.scrap, np.int32)
            rem = np.ones(B, np.int32)
            rids = np.zeros(B, np.int32)
            for j, i in enumerate(dr_ix):
                r = reqs[i]
                gen = self.generated[r.rid]
                prompt = self.prompt_ids(r)
                dr = drafts[i]
                toks[j, 0] = gen[-1] if gen else prompt[-1]
                toks[j, 1:1 + len(dr)] = dr
                pos0[j] = r.prompt_len - 1 + r.decoded
                widths[j] = 1 + len(dr)
                tabs[j] = self._padded_table(r.rid, tables[i])
                rem[j] = max(1, r.true_output_len - r.decoded)
                rids[j] = r.rid & 0x7FFFFFFF
        t0 = time.perf_counter()
        with self._launch():
            targets, emitted, self.pages = self._get_verify_fn()(
                self.params, self.pages, jnp.asarray(toks),
                jnp.asarray(pos0), jnp.asarray(widths), jnp.asarray(tabs),
                jnp.asarray(rem), jnp.asarray(rids))
        with span("backend.wait"):
            targets = np.asarray(targets)    # ONE host sync per step
            emitted = np.asarray(emitted)
        self._t_acc += time.perf_counter() - t0
        self.n_decode_dispatches += 1
        with span("backend.unpack"):
            for j, i in enumerate(dr_ix):
                r = reqs[i]
                e = int(emitted[j])
                self.generated[r.rid].extend(int(t) for t in targets[j, :e])
                out[i] = (e, e - 1, len(drafts[i]))
        # decode_batch_n already counted the plain lanes' tokens
        self.n_decode_tokens += sum(out[i][0] for i in dr_ix)
        return out

    # -- KV residency hooks (mirror BlockManager transitions 1:1) -------
    @staticmethod
    def _page_axis(path) -> int:
        """Where the page index sits in a pool leaf: after the layer dim
        for the scanned units' stacked pools, first otherwise (whatever
        a pool's row shape)."""
        return 1 if getattr(path[0], "key", None) == "units" else 0

    def _gather_pages(self, table):
        """Host copies of the pages ``table`` lists, in every pool."""
        return jax.tree_util.tree_map_with_path(
            lambda path, p: np.asarray(jnp.take(p, table,
                                                axis=self._page_axis(path))),
            self.pages)

    def _scatter_pages(self, table, saved) -> None:
        def put(path, p, s):
            idx = (slice(None),) * self._page_axis(path) + (table,)
            return p.at[idx].set(jnp.asarray(s, p.dtype))

        self.pages = jax.tree_util.tree_map_with_path(put, self.pages, saved)
        self._commit_pages()

    def kv_swap_out(self, rid: int, block_table: List[int],
                    tokens: int) -> None:
        self._tab_cache.pop(rid, None)
        if not block_table:
            return
        self._flush_prefill()     # the gather must see this step's writes
        self._host[rid] = self._gather_pages(
            np.asarray(block_table, np.int32))

    def kv_swap_in(self, rid: int, block_table: List[int]) -> None:
        saved = self._host.pop(rid, None)
        if saved is None:
            return
        self._scatter_pages(np.asarray(block_table, np.int32), saved)

    def kv_copy_page(self, src: int, dst: int) -> None:
        """COW fork: duplicate device page src into dst (the engine is
        about to append into a previously shared page).  Byte-exact copy,
        so forked continuations equal their cache-off counterparts."""
        self._flush_prefill()     # src must hold this step's writes

        def copy(path, p):
            lead = (slice(None),) * self._page_axis(path)
            return p.at[lead + (dst,)].set(p[lead + (src,)])

        self.pages = jax.tree_util.tree_map_with_path(copy, self.pages)
        self._commit_pages()

    def kv_release(self, rid: int) -> None:
        self._host.pop(rid, None)
        self._prompts.pop(rid, None)
        self._tab_cache.pop(rid, None)

    # -- live KV migration (DESIGN.md §12) ------------------------------
    def kv_export_pages(self, rid: int, block_table: List[int]):
        """Host-staged export for replica-to-replica migration: gather
        rid's page contents to host numpy (the kv_swap_out path) and
        bundle the prompt + generated-token state the destination needs to
        continue the stream byte-identically — sampling is keyed
        (seed, rid, pos), so with the same backend seed the destination
        reproduces exactly the tokens this replica would have emitted.
        Per-request local state is dropped: after export the request lives
        on the destination.  The device pages themselves are NOT cleared —
        the engine may first register them into its prefix index so local
        followers still match the prefill this replica paid for."""
        self._flush_prefill()     # the gather must see this step's writes
        if block_table:
            pages = self._gather_pages(np.asarray(block_table, np.int32))
        else:
            # swapped-out at export time: the host copy IS the content
            pages = self._host.get(rid)
        payload = dict(pages=pages,
                       prompt=self._prompts.pop(rid, None),
                       generated=self.generated.pop(rid, None))
        self._host.pop(rid, None)
        self._tab_cache.pop(rid, None)
        return payload

    def kv_import_pages(self, rid: int, payload,
                        block_table: Optional[List[int]]) -> None:
        """Install an exported payload: adopt the prompt/generated state
        (so (seed, rid, pos) sampling keys line up) and scatter the page
        contents into this pool — or park them host-side when
        ``block_table`` is None (arrival under pool pressure; the ordinary
        kv_swap_in path restores them once the engine frees blocks)."""
        if payload is None:
            return
        if payload.get("prompt") is not None:
            self._prompts[rid] = payload["prompt"]
        if payload.get("generated") is not None:
            self.generated[rid] = list(payload["generated"])
        pages = payload.get("pages")
        if pages is None:
            return
        if block_table:
            self._scatter_pages(np.asarray(block_table, np.int32), pages)
        else:
            self._host[rid] = pages

    def output_tokens(self, rid: int) -> Optional[List[int]]:
        """Real generated tokens — the engine registers prompt+output
        pages into the prefix cache under their TRUE content hash (the
        workload's synthetic output tokens would mis-describe real KV)."""
        return self.generated.get(rid)

    # ------------------------------------------------------------------
    def step_time(self, prefill_tokens: int, decode_ctxs: List[int],
                  verify_tokens: int = 0) -> float:
        # verify_tokens is a cost-model hint; wall time already includes
        # the verification dispatch, so it is accepted and ignored here
        self._flush_prefill()
        # the step's one host sync: drain every dispatch queued above
        t0 = time.perf_counter()
        with span("backend.wait"):
            jax.tree.leaves(self.pages)[0].block_until_ready()
        self._t_acc += time.perf_counter() - t0
        if self._counts_pending:
            self._m_routed["prefill"].inc(
                int(sum(np.asarray(c).sum() for c in self._counts_pending)))
            self._counts_pending = []
        self._m_pages.inc(self._pages_step)
        return self.overhead + self._t_acc
