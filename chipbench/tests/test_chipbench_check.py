"""The output check that decides ``correct``, with the timed path broken
underneath: the harness runs on the CPU at a tiny size (the rehearsal path,
which skips the look for a chip) and must come out not correct for each
fault a one-chip serving cell can have, with the cell's own limit.  The
same runs unbroken come out correct.  The control (the reference computed
in float8, the precision below the configuration's bfloat16) is read at a
size a test run can hold, and must read above the limit there too."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.dirname(os.path.abspath(__file__)), ROOT,
                os.path.join(ROOT, "src")]

from test_chipbench_spec import rehearsal  # noqa: E402

CELL = "yi34b-8l.longdoc"


@pytest.fixture(scope="module")
def bench():
    from chipbench import run
    _, _, cfg, _, _ = run.find_cell(CELL)
    return run, run.build(CELL, 2**31 + 99, 3.0, rehearse=rehearsal(cfg))


def _serve_and_check(bench, seed):
    from chipbench import serve as sv
    from chipbench import traffic as tr
    run, s = bench
    mix = s["mix"]
    arrs = tr.arrivals(mix, mix["preroll_s"] + 3.0, seed)
    toks = tr.prompt_tokens(arrs, s["k"]["V"], seed)
    s["be"].reset_run_state()
    res = sv.serve(s["be"], arrs, toks, s["warm"], mix["preroll_s"], 3.0)
    res.pop("engine")
    readings, _ = run.check(res, toks, s["cfg"], s["weights"], s["ref"],
                            mix["compare_requests"], seed)
    cmp = run.correct_of(readings, s["limits"])
    return run.is_correct(cmp), readings


def _wrap(monkeypatch, obj, name, make):
    monkeypatch.setattr(obj, name, make(getattr(obj, name)))


def test_sound_run_is_correct(bench):
    ok, readings = _serve_and_check(bench, 5)
    assert ok, readings
    assert readings["tokens_checked"] > 20


def test_step_that_leaves_its_state_unchanged(bench, monkeypatch):
    """Decode returns the page pool as it found it: this step's keys and
    values are never written."""
    be = bench[1]["be"]

    def make(fn):
        def call(*a, **k):
            pages = be.pages
            out = fn(*a, **k)
            be.pages = pages
            return out
        return call

    _wrap(monkeypatch, be, "decode_batch_n", make)
    ok, readings = _serve_and_check(bench, 6)
    assert not ok, readings


def test_half_of_the_batch_left_out(bench, monkeypatch):
    """Every other prefill chunk of a step is dropped: half of the prompt
    lanes never reach the pool."""
    be = bench[1]["be"]
    calls = [0]

    def make(fn):
        def call(req, start, n, table):
            calls[0] += 1
            if calls[0] % 2:
                be.generated.setdefault(req.rid, [])
                return None
            return fn(req, start, n, table)
        return call

    _wrap(monkeypatch, be, "prefill_chunk", make)
    ok, readings = _serve_and_check(bench, 7)
    assert not ok, readings


def test_token_altered_where_it_is_produced(bench, monkeypatch):
    """One sampled token in twenty is replaced as the sampler returns it
    (and fed back as the next input, as a wrong sample would be)."""
    be = bench[1]["be"]
    V = bench[1]["k"]["V"]
    calls = [0]

    def make(fn):
        def call(reqs, tables, n):
            tok, act = fn(reqs, tables, n)
            calls[0] += 1
            if calls[0] % 20 == 0 and len(reqs):
                gen = be.generated[reqs[0].rid]
                gen[-1] = (gen[-1] + 1) % V
            return tok, act
        return call

    _wrap(monkeypatch, be, "decode_batch_n", make)
    ok, readings = _serve_and_check(bench, 8)
    assert not ok, readings


def test_control_reads_above_the_limit():
    """The float8 control on seeded weights and tokens, at a size the CPU
    holds (d_model 1536, 16 layers, vocabulary 32768, 512 positions): the
    widest gap of the token it puts first lies above the cell's limit (on
    the chip, at the cell's own size, it read 2 to 8 times higher)."""
    import importlib.util
    import jax
    spec = importlib.util.spec_from_file_location(
        "dense_ref", os.path.join(ROOT, "chipbench", "references",
                                  "dense_gqa.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "yi-34b-8l.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "cells", CELL + ".json")) as f:
        limit = json.load(f)["max_logit_gap"]
    cfg.update(hidden_size=1536, intermediate_size=4608,
               num_attention_heads=12, num_key_value_heads=3, head_dim=128,
               num_hidden_layers=16, vocab_size=32768)
    w = ref.make_weights(cfg, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(0, 32768, 512)
    _, ctrl = ref.gaps(cfg, w, toks[:256], toks[256:], fp8_control=True)
    assert ctrl.max() > limit, (ctrl.max(), limit)
