"""Smoke run of the serving path on a TPU — a start-up proof, not a benchmark.

    python chip_smoke.py [--seed N]            # one chip
    python chip_smoke.py --tp 4 [--seed N]     # tp=4 over four chips vs tp=1

Serves tinyllama-1.1b at its published widths (22 layers, d_model 2048,
32 query / 4 KV heads of 64, d_ff 5632, vocab 32000, bf16; random weights
from ``--seed``) through the normal path:
``run(ExperimentSpec(...))`` -> ``ServeEngine`` -> ``gmg`` scheduler ->
``BlockManager`` -> ``PagedJaxBackend`` -> compiled Pallas kernels.

One chip (default), in order:

1. kernel — the verify kernel (one grid pass over a W-row window) against
   W chained single-row ``fused_decode_attention`` calls on the same
   inputs: outputs within ``KERNEL_TOL``, page write-backs equal; at
   tinyllama's head widths and at Yi-34B's (``WIDE_HEADS``, where the
   decode kernel DMAs the pool itself).
2. serve  — a dozen requests (prompts of a few hundred tokens, outputs up
   to 128) with multi-step decode; every request must finish with tokens.
3. logits — for two served requests, the backend's own compiled prefill
   and decode step, teacher-forced with the served tokens, against the
   float32 non-paged ``Model.logits`` forward on the same weights (last
   prompt position plus the first decoded ones), within ``LOGIT_REL_TOL``.
4. spec   — the same workload with ``spec_depth_max=4``: the verify
   dispatch must run; agreement with the spec-off streams is printed (in
   bf16 a different batch width can flip a greedy token, so it is
   reported, not gated).

``--tp 4`` runs only the tensor-parallel path: the same requests served
at tp=4 across four chips and at tp=1 on one chip of the same host, both
checked against the float32 reference with the same tolerance, and the
greedy agreement between the two printed.

The page pool is sized from the device's memory.  The jitted steps do not
donate the pool, and one that carries it through a ``lax.scan`` (multi-step
decode, batched prefill) holds it about five times over on a v5e: the
argument, loop-carried copies, and the 64-wide head dim padded to 128
lanes (XLA's memory report for the full-width decode scan compiled for a
v5e).  So the pool gets a sixth of what the weights and a reserve leave.

Exits non-zero, printing no result, when JAX finds no TPU or any check
fails.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402

from repro.configs.base import get_config                      # noqa: E402
from repro.kernels.paged_attention import (                    # noqa: E402
    _verify_multirow, _verify_unrolled)
from repro.models.model import build_model                     # noqa: E402
from repro.serving.engine import EngineConfig                  # noqa: E402
from repro.serving.jax_backend import _bucket                  # noqa: E402
from repro.serving.run import (BackendSpec, ExperimentSpec,    # noqa: E402
                               enable_compile_cache, run)
from repro.serving.workload import WorkloadGen, WorkloadSpec   # noqa: E402

ARCH = "tinyllama-1.1b"
PAGE = 16                  # the backend's default page size
MAX_LEN = 2048
DECODE_STEPS = 4
SPEC_DEPTH = 4
CHECKED_DECODES = 4        # decoded positions checked after the prompt's last

# Served logits vs the float32 reference, as ||served - ref|| / ||ref||
# over every checked position.  The served model rounds weights-times-
# activations to bf16 (8-bit mantissa, unit roundoff 2^-9) at every
# projection of every layer, and those roundings accumulate through the
# residual stream; the reference keeps the same bf16 weights but computes
# in float32 at "highest" matmul precision.  That drift is 0.0225 at the
# published widths on a TPU v5e (seed 0) and 0.004-0.01 at reduced widths
# on the CPU, where the same served logits sit 0.23-0.27 from a reference
# missing one layer; lost context moves them by more than 1.
LOGIT_REL_TOL = 0.05

# Verify kernel vs chained single-row decode kernel: both accumulate in
# float32 and round the output to bf16 once, so they may differ by about
# one bf16 ulp (2^-8 relative) of the largest output.
KERNEL_TOL = 1e-2
WIDE_HEADS = "yi-34b"      # 56 query / 8 KV heads of 128, for the kernel

# Device memory kept out of the pool: activations, sampler, compile
# scratch.
RESERVE_BYTES = 2 << 30


class _CompileClock:
    """Sums JAX's backend compile (or persistent-cache load) durations."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    v = stats.get("peak_bytes_in_use")
    return "not reported" if v is None else str(v)


# ---------------------------------------------------------------------------
# sizing and workload
# ---------------------------------------------------------------------------
def pool_blocks(cfg, device, page: int = PAGE) -> int:
    """Pages per device: a sixth of the memory the weights and
    ``RESERVE_BYTES`` leave (see the module docstring)."""
    stats = device.memory_stats() or {}
    limit = stats.get("bytes_limit")
    if not limit:
        fail(f"{device.device_kind} reports no bytes_limit to size the pool")
    weights = cfg.param_count() * jnp.dtype(cfg.dtype).itemsize
    page_bytes = build_model(cfg).kv_bytes_per_token() * page
    n = int((limit - weights - RESERVE_BYTES) // 6 // page_bytes)
    if n * page < MAX_LEN:
        fail(f"pool of {n} pages cannot hold one {MAX_LEN}-token sequence")
    return n


def workload(seed: int) -> WorkloadSpec:
    """About a dozen single requests arriving within ~1.5 s, each either
    streaming chat with latency SLOs or best-effort; prompts are a
    256-token system prefix plus up to 256 tokens, outputs up to 128.
    Prompt tokens are drawn by the backend over the full served
    vocabulary."""
    return WorkloadSpec(rate=8.0, duration=1.5, seed=seed, mix=(1, 0, 0),
                        best_effort_frac=0.5, prompt_cap=256,
                        output_cap=128, system_prompt_len=256,
                        shared_system_frac=1.0, slo_scale=50.0)


def serve(cfg, wl: WorkloadSpec, num_blocks: int, seed: int, *,
          tp: int = 1, spec_depth: int = 0, max_len: int = MAX_LEN,
          page: int = PAGE):
    """Serve ``wl`` through ``run(ExperimentSpec(...))`` and check that
    every request finished with all its tokens.  Returns (summary,
    backend, requests)."""
    sink = []
    summ = run(ExperimentSpec(
        scheduler="gmg", workload=wl,
        engine=EngineConfig(max_batch=16, prefill_budget=2048,
                            decode_steps=DECODE_STEPS,
                            spec_depth_max=spec_depth, tp=tp,
                            max_steps=20_000),
        backend=BackendSpec(kind="jax", sink=sink, kwargs=dict(
            arch=cfg, num_blocks=num_blocks, page=page, max_len=max_len,
            seed=seed, tp=tp))))
    be = sink[0]
    reqs, _ = WorkloadGen(wl).generate()    # singles; the mix has no DAGs
    if not reqs:
        fail("the workload produced no requests")
    if summ.n_finished != len(reqs):
        fail(f"{summ.n_finished} of {len(reqs)} requests finished")
    for r in reqs:
        got = len(be.generated.get(r.rid, ()))
        if got == 0 or got != r.true_output_len:
            fail(f"request {r.rid} produced {got} of {r.true_output_len} "
                 "tokens")
    return summ, be, reqs


def streams(be, reqs):
    return {r.rid: list(be.generated[r.rid]) for r in reqs}


def stream_agreement(a, b):
    """Token streams {rid: tokens} compared position by position:
    (agreeing tokens, total tokens, identical streams)."""
    same = total = ident = 0
    for rid, x in a.items():
        y = b[rid]
        total += len(x)
        same += sum(int(p == q) for p, q in zip(x, y))
        ident += int(x == y)
    return same, total, ident


# ---------------------------------------------------------------------------
# correctness: served logits vs the float32 reference
# ---------------------------------------------------------------------------
def teacher_forced_logits(be, items, k: int = CHECKED_DECODES):
    """Logits of the backend's own compiled prefill and decode step for
    ``items`` = [(prompt tokens, served tokens)], teacher-forced with the
    served tokens: (len(items), k+1, vocab) at the prompt's last position
    and the next ``k``.  Writes fresh pages of ``be.pages``."""
    B = len(items)
    tabs = np.full((B, be.n_max), be.scrap, np.int32)
    for i, (prompt, _) in enumerate(items):
        need = -(-(len(prompt) + k) // be.page)
        tabs[i, :need] = np.arange(i * be.n_max, i * be.n_max + need)
    pages = be.pages
    for i, (prompt, _) in enumerate(items):
        C = _bucket(len(prompt))
        toks = np.zeros((1, C), np.int32)
        toks[0, :len(prompt)] = prompt
        pages, _ = be._prefill(be.params, pages, jnp.asarray(toks),
                               jnp.int32(0), jnp.asarray(tabs[i]),
                               jnp.int32(len(prompt)))
    out = []
    for s in range(k + 1):
        toks = np.array([[prompt[-1] if s == 0 else served[s - 1]]
                         for prompt, served in items], np.int32)
        pos = np.array([len(prompt) - 1 + s for prompt, _ in items],
                       np.int32)
        logits, pages, _ = be._decode(be.params, pages, jnp.asarray(toks),
                                      jnp.asarray(pos), jnp.asarray(tabs))
        out.append(np.asarray(logits, np.float32))
    be.pages = pages
    return np.stack(out, axis=1)


def reference_logits(cfg, params, items, k: int = CHECKED_DECODES):
    """The non-paged ``Model.logits`` forward in float32 on the same
    weights, one sequence at a time: (len(items), k+1, vocab) at the same
    positions as ``teacher_forced_logits``."""
    model = build_model(dataclasses.replace(cfg, dtype="float32"))

    def fwd(p, t):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        return model.logits(p, {"tokens": t})

    out = []
    with jax.default_matmul_precision("highest"):
        f = jax.jit(fwd)
        for prompt, served in items:
            seq = np.concatenate([prompt, served[:k]]).astype(np.int32)
            lg = np.asarray(f(params, jnp.asarray(seq)[None]), np.float32)[0]
            out.append(lg[len(prompt) - 1:len(prompt) + k])
    return np.stack(out)


def logit_error(got, ref) -> float:
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def check_logits(name: str, got, ref) -> float:
    err = logit_error(got, ref)
    max_abs = float(np.max(np.abs(got - ref)))
    say(f"{name}: logit rel-L2 error vs float32 reference {err!r} "
        f"(max abs {max_abs!r}, tolerance {LOGIT_REL_TOL})")
    if not np.isfinite(got).all():
        fail(f"{name}: non-finite served logits")
    if err > LOGIT_REL_TOL:
        fail(f"{name}: logit error {err} exceeds {LOGIT_REL_TOL}")
    return err


def checked_items(be, reqs, n: int = 2):
    """(prompt, served tokens) of the ``n`` longest-prompt requests."""
    pick = sorted(reqs, key=lambda r: (-r.prompt_len, r.rid))[:n]
    return [(be.prompt_ids(r), np.asarray(be.generated[r.rid], np.int32))
            for r in pick]


def check_compiled_kernels(be) -> None:
    """The served decode step must lower to compiled TPU kernels."""
    if be.interpret:
        fail("the backend resolved interpret=True: kernels would be "
             "interpreted")
    txt = be._decode.lower(
        be.params, be.pages, jnp.zeros((1, 1), jnp.int32),
        jnp.zeros((1,), jnp.int32),
        jnp.full((1, be.n_max), be.scrap, jnp.int32)).as_text()
    if "tpu_custom_call" not in txt:
        fail("the decode step holds no compiled TPU kernel")


def release(be):
    """Drop the backend's device pool and return its weights."""
    params = be.params
    be.pages = be.params = None
    be._host.clear()
    gc.collect()
    return params


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def verify_kernel_error(cfg, seed: int, *, B: int = 8, W: int = 4,
                        n_max: int = 8, interpret: bool = False):
    """The chip lowering of verification (one grid pass over the W-row
    window) vs W chained single-row fused decode calls, on the same
    random inputs.  Returns (max output error / max |output|, page
    write-backs equal outside the scrap page)."""
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    P = B * n_max + 1
    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16

    def rnd(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), bf)

    q, kn, vn = rnd(B, W, H, D), rnd(B, W, KV, D), rnd(B, W, KV, D)
    kp, vp = rnd(P, PAGE, KV, D), rnd(P, PAGE, KV, D)
    tabs = jnp.asarray(np.arange(B * n_max, dtype=np.int32)
                       .reshape(B, n_max))
    pos0 = jnp.asarray(rng.integers(0, n_max * PAGE - W, B), jnp.int32)
    widths = jnp.asarray(np.r_[W, rng.integers(1, W + 1, B - 1)],
                         jnp.int32)
    multi = jax.jit(functools.partial(_verify_multirow,
                                      interpret=interpret))
    chain = jax.jit(functools.partial(_verify_unrolled,
                                      interpret=interpret))
    o_m, kp_m, vp_m = multi(q, kn, vn, kp, vp, tabs, pos0, widths)
    o_c, kp_c, vp_c = chain(q, kn, vn, kp, vp, tabs, pos0, widths)
    live = np.arange(W)[None, :] < np.asarray(widths)[:, None]   # (B, W)
    o_m = np.asarray(o_m, np.float32)[live]
    o_c = np.asarray(o_c, np.float32)[live]
    err = float(np.max(np.abs(o_m - o_c)) / np.max(np.abs(o_c)))
    pages_equal = all(
        np.array_equal(np.asarray(a[:-1]), np.asarray(b[:-1]))
        for a, b in ((kp_m, kp_c), (vp_m, vp_c)))
    return err, pages_equal


def phase_kernel(cfg, seed: int) -> None:
    err, pages_equal = verify_kernel_error(cfg, seed)
    say(f"verify kernel vs chained single-row decode at {cfg.name} head "
        f"widths: max error {err!r} of max |output| (tolerance "
        f"{KERNEL_TOL}); page write-backs "
        f"{'equal' if pages_equal else 'DIFFER'}")
    if not np.isfinite(err) or err > KERNEL_TOL:
        fail(f"verify kernel error {err} exceeds {KERNEL_TOL}")
    if not pages_equal:
        fail("verify kernel page write-backs differ from chained decode")


def one_chip(args, clock, device) -> None:
    cfg = get_config(ARCH)
    phase_kernel(cfg, args.seed)
    # 128-wide heads: the decode kernel DMAs the pool itself (64-wide
    # ones take the grid's page windows)
    phase_kernel(get_config(WIDE_HEADS), args.seed)

    nb = pool_blocks(cfg, device)
    wl = workload(args.seed)
    say(f"pool: {nb} pages of {PAGE} tokens "
        f"({nb * build_model(cfg).kv_bytes_per_token() * PAGE} bytes)")

    c0, t0 = clock.seconds, time.perf_counter()
    summ, be, reqs = serve(cfg, wl, nb, args.seed)
    wall = time.perf_counter() - t0
    say(f"serve (spec off): {len(reqs)} requests, prompts "
        f"{min(r.prompt_len for r in reqs)}-"
        f"{max(r.prompt_len for r in reqs)} tokens, "
        f"{sum(r.true_output_len for r in reqs)} tokens produced in "
        f"{be.n_decode_dispatches} decode dispatches; wall {wall!r} s, of "
        f"which compile {clock.seconds - c0!r} s; "
        f"peak_bytes_in_use {peak_bytes(device)}")

    items = checked_items(be, reqs)
    check_compiled_kernels(be)
    got = teacher_forced_logits(be, items)
    agree = sum(int(np.argmax(got[i, s]) == served[s])
                for i, (_, served) in enumerate(items)
                for s in range(CHECKED_DECODES))
    say(f"teacher-forced greedy tokens equal to the served ones: "
        f"{agree}/{len(items) * CHECKED_DECODES}")
    plain = streams(be, reqs)
    params = release(be)
    del be
    ref = reference_logits(cfg, params, items)
    check_logits("serve", got, ref)
    del params
    gc.collect()

    c0, t0 = clock.seconds, time.perf_counter()
    summ, be, _ = serve(cfg, wl, nb, args.seed, spec_depth=SPEC_DEPTH)
    wall = time.perf_counter() - t0
    if summ.spec_proposed <= 0 or be._verify_fn is None:
        fail("speculation proposed no draft: the verify kernel never ran")
    same, total, ident = stream_agreement(streams(be, reqs), plain)
    say(f"serve (spec on, depth <= {SPEC_DEPTH}): drafted "
        f"{summ.spec_proposed}, accepted {summ.spec_accepted}; wall "
        f"{wall!r} s, of which compile {clock.seconds - c0!r} s; "
        f"peak_bytes_in_use {peak_bytes(device)}")
    say(f"spec-on vs spec-off: {same}/{total} tokens agree, {ident}/"
        f"{len(reqs)} streams identical")
    release(be)


def tensor_parallel(args, clock, device) -> None:
    tp = args.tp
    if len(jax.devices()) < tp:
        fail(f"--tp {tp} needs {tp} chips, JAX sees {len(jax.devices())}")
    cfg = get_config(ARCH)
    nb = pool_blocks(cfg, device)
    wl = workload(args.seed)
    runs = {}
    for t in (tp, 1):
        c0, t0 = clock.seconds, time.perf_counter()
        _, be, reqs = serve(cfg, wl, nb, args.seed, tp=t)
        wall = time.perf_counter() - t0
        say(f"serve tp={t}: {len(reqs)} requests, "
            f"{sum(r.true_output_len for r in reqs)} tokens; wall {wall!r} "
            f"s, of which compile {clock.seconds - c0!r} s; "
            f"peak_bytes_in_use {peak_bytes(device)}")
        items = checked_items(be, reqs)
        check_compiled_kernels(be)
        runs[t] = (teacher_forced_logits(be, items), streams(be, reqs),
                   items)
        params = release(be)
        del be
    for t in (tp, 1):
        # each run is teacher-forced with its own served tokens
        check_logits(f"tp={t}", runs[t][0],
                     reference_logits(cfg, params, runs[t][2]))
    same, total, ident = stream_agreement(runs[tp][1], runs[1][1])
    say(f"tp={tp} vs tp=1: {same}/{total} greedy tokens agree, {ident}/"
        f"{len(runs[1][1])} streams identical")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, prompts and workload")
    ap.add_argument("--tp", type=int, default=1, choices=(1, 4),
                    help="4: only the tensor-parallel path over four chips "
                    "and its tp=1 comparison")
    args = ap.parse_args(argv)

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{device.platform!r} ({device.device_kind})", file=sys.stderr)
        return 2
    say("smoke run, not a benchmark: the numbers below are start-up "
        "evidence, not measurements of serving speed")
    say(f"device: {device.device_kind} x{len(jax.devices())}; compile "
        f"cache: {enable_compile_cache()}")
    clock = _CompileClock()
    t0 = time.perf_counter()
    if args.tp == 1:
        one_chip(args, clock, device)
    else:
        tensor_parallel(args, clock, device)
    say(f"total wall {time.perf_counter() - t0!r} s, compile "
        f"{clock.seconds!r} s over {clock.count} compiles")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
