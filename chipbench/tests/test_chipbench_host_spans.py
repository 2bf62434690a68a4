"""The program's host spans and the readers of the host-time metrics.

A tiny engine is served on the CPU under the JAX profiler with the
harness's wrappers applied, and its trace read as the harness reads it:
every span of the program's table is recorded, each inside an
``engine.step_once``, and the streams match a run without the profiler.
The readers are checked on hand-built spans."""

import gc
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import host_spans as hsp  # noqa: E402
from chipbench import trace_reduce as trr  # noqa: E402

HARNESS_SPANS = ("engine.", "sched.", "backend.", "harness.")
E = trr.Event


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(ROOT, "chipbench", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the program's spans in a served run -----------------------------------
def _serve(trace_dir=None):
    """Six requests (chunked prompts, a few decoded tokens each) served to
    the end through the harness's engine; with ``trace_dir``, under the
    profiler with the harness's wrappers and frequent garbage
    collections."""
    import jax
    from chipbench import serve as sv
    from repro.serving.jax_backend import PagedJaxBackend
    from repro.serving.request import Request, SLOSpec
    be = PagedJaxBackend(num_blocks=32, page=16, max_len=64, seed=3)
    eng = sv._engine(be)
    rng = np.random.default_rng(5)
    for i in range(6):
        n = int(rng.integers(9, 40))
        r = Request(rid=i + 1, app="chatbot", arrival=0.01 * i,
                    prompt_len=n, true_output_len=int(rng.integers(2, 6)),
                    slo=SLOSpec("latency", ttft=5.0, tbt=1.0))
        r.meta["prompt_tokens"] = rng.integers(0, be.cfg.vocab_size, n)
        eng.enqueue("r", r)
    if trace_dir is None:
        while eng.step_once():
            pass
        return be.generated, None
    ann = sv.annotate(eng)
    old = gc.get_threshold()
    gc.set_threshold(50)
    try:
        with jax.profiler.trace(trace_dir):
            while eng.step_once():
                pass
    finally:
        gc.set_threshold(*old)
        ann.undo()
    return be.generated, trr.load(trr.find_xplane(trace_dir), HARNESS_SPANS)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    plain, _ = _serve()
    traced, tr = _serve(str(tmp_path_factory.mktemp("trace")))
    return plain, traced, tr


def test_every_span_recorded_inside_a_step(served):
    from repro.obs import SPANS
    _, _, tr = served
    assert set(SPANS) == set(hsp.PROGRAM)
    host = [e for e, _ in tr.host]
    steps = [e for e in host if e.name == hsp.STEP]
    program = [e for e in host if e.name in SPANS]
    assert steps and {e.name for e in program} == set(SPANS)
    for e in program:
        assert any(s.start <= e.start and e.end <= s.end for s in steps), e
    # no program span is named like one of the harness's wrappers
    wrappers = {e.name for e in host} - set(SPANS)
    assert wrappers <= {hsp.STEP, "sched.schedule"} | {
        f"backend.{m}" for m in ("prefill_chunk", "decode_batch",
                                 "decode_batch_n", "step_time",
                                 "kv_swap_out", "kv_swap_in",
                                 "kv_copy_page")}


def test_streams_identical_with_the_profiler_on(served):
    plain, traced, _ = served
    assert traced == plain and any(plain.values())


def test_readers_on_the_served_trace(served):
    _, _, tr = served
    ctx = dict(host=tr.host)
    steps = [e for e, _ in tr.host if e.name == hsp.STEP]
    mean_ms = sum(e.end - e.start for e in steps) / len(steps) / 1e6
    host = _reader("engine.host_ms").read(ctx)
    sched = _reader("sched.host_ms").read(ctx)
    stage = _reader("step.stage_ms").read(ctx)
    assert 0 < host <= mean_ms
    assert 0 < sched < host and 0 < stage < host


# -- the readers on hand-built spans ---------------------------------------
def _step(t, wait=4.0, names=("sched.refine", "sched.group", "sched.fill",
                              "backend.stage", "backend.launch",
                              "backend.unpack")):
    """One 10 ms engine step from ``t`` ms: 1 ms per named span, then
    ``wait`` ms in backend.wait; times in ns."""
    ms = 1_000_000
    out = [(E(hsp.STEP, t * ms, (t + 10) * ms), 0)]
    c = t
    for n in names:
        out.append((E(n, c * ms, (c + 1) * ms), 1))
        c += 1
    if wait:
        out.append((E("backend.wait", c * ms, int((c + wait) * ms)), 2))
    return out


def test_readers_on_hand_built_steps():
    host = _step(0) + _step(20, wait=2.0) + [(E("harness.wait", 12 * 10**6,
                                                 18 * 10**6), 0)]
    ctx = dict(host=host)
    assert _reader("engine.host_ms").read(ctx) == pytest.approx(7.0)
    assert _reader("sched.host_ms").read(ctx) == pytest.approx(3.0)
    assert _reader("step.stage_ms").read(ctx) == pytest.approx(3.0)
    # a span outside every step is left out
    ctx["host"] = host + [(E("sched.fill", 50 * 10**6, 60 * 10**6), 0)]
    assert _reader("sched.host_ms").read(ctx) == pytest.approx(3.0)


@pytest.mark.parametrize("name", ["engine.host_ms", "sched.host_ms",
                                  "step.stage_ms"])
def test_readers_refuse_steps_that_never_wait(name):
    ctx = dict(host=_step(0, wait=0) + _step(20, wait=0))
    with pytest.raises(ValueError, match="backend.wait"):
        _reader(name).read(ctx)


@pytest.mark.parametrize("name,kept", [
    ("sched.host_ms", ("backend.stage", "backend.launch")),
    ("step.stage_ms", ("sched.refine", "sched.fill")),
])
def test_readers_refuse_a_window_without_their_spans(name, kept):
    ctx = dict(host=_step(0, names=kept) + _step(20, names=kept))
    with pytest.raises(ValueError, match="no span named"):
        _reader(name).read(ctx)


@pytest.mark.parametrize("name", ["engine.host_ms", "sched.host_ms",
                                  "step.stage_ms"])
def test_readers_give_nothing_for_a_program_without_spans(name):
    harness_only = [(E(hsp.STEP, 0, 10), 0), (E("sched.schedule", 1, 3), 1),
                    (E("backend.decode_batch_n", 4, 9), 1)]
    assert _reader(name).read(dict(host=harness_only)) is None
    assert _reader(name).read({}) is None


def test_host_spans_found_beside_the_harness_ctx():
    """Where the harness keeps the loaded trace in ``main`` and hands the
    readers only the reduction, the spans are found on the stack."""
    class Loaded:
        host = _step(0)

    def main():
        tr_ = Loaded()          # noqa: F841 (read from the stack)
        ctx = dict(trace={})
        return per_layer(ctx)

    def per_layer(ctx):
        return _reader("engine.host_ms").read(ctx)

    assert main() == pytest.approx(6.0)
