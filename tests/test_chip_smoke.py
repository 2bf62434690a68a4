"""The phases of ``chip_smoke.py`` at reduced width on the CPU, so the
chip's start-up proof keeps working between chip runs: the platform
refusal, the verify-kernel comparison (the multi-row chip lowering
against chained single-row decode, interpreted), and the served-logits
check against the float32 non-paged reference — including that its
tolerance is tight enough to catch a missing layer."""

import dataclasses
import os
import sys
import types

import pytest

from repro.configs.archs import reduced_config
from repro.configs.base import get_config

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def cs():
    # imported here, not at collection: chip_smoke imports JAX, and test
    # files collected later (test_disagg, test_tp) set XLA_FLAGS for
    # virtual devices before their own first JAX import
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    return chip_smoke


def test_chip_smoke_refuses_a_host_without_tpu(cs, capsys, monkeypatch):
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    monkeypatch.setattr(cs.jax, "devices", lambda *a: [cpu])
    assert cs.main([]) == 2
    out, err = capsys.readouterr()
    assert "needs a TPU" in err and "'cpu'" in err
    assert out == ""                       # no result line


def test_verify_kernel_lowerings_agree_at_tinyllama_head_widths(cs):
    err, pages_equal = cs.verify_kernel_error(get_config(cs.ARCH), 0,
                                              interpret=True)
    assert err <= cs.KERNEL_TOL, err
    assert pages_equal


def test_verify_kernel_lowerings_agree_at_wide_head_widths(cs):
    err, pages_equal = cs.verify_kernel_error(get_config(cs.WIDE_HEADS), 0,
                                              interpret=True)
    assert err <= cs.KERNEL_TOL, err
    assert pages_equal


@pytest.fixture(scope="module")
def served(cs):
    """A reduced two-layer tinyllama in bf16 served through the smoke's
    ``serve`` (run -> engine -> gmg -> backend), plus its checked
    teacher-forced logits and weights."""
    cfg = dataclasses.replace(reduced_config(cs.ARCH), dtype="bfloat16",
                              num_layers=2)
    wl = dataclasses.replace(cs.workload(0), rate=3.0, prompt_cap=32,
                             system_prompt_len=32, output_cap=8)
    summ, be, reqs = cs.serve(cfg, wl, 64, 0, max_len=128)
    items = cs.checked_items(be, reqs)
    got = cs.teacher_forced_logits(be, items)
    return cfg, reqs, items, got, cs.release(be)


def test_served_logits_match_float32_reference(cs, served):
    cfg, reqs, items, got, params = served
    assert len(reqs) >= 2 and len(items) == 2
    ref = cs.reference_logits(cfg, params, items)
    assert got.shape == ref.shape == (2, cs.CHECKED_DECODES + 1,
                                      cfg.vocab_size)
    assert cs.check_logits("cpu", got, ref) <= cs.LOGIT_REL_TOL


def test_logit_tolerance_catches_a_missing_layer(cs, served):
    cfg, _, items, got, params = served
    one = dict(params, units={k: {n: a[:1] for n, a in v.items()}
                              for k, v in params["units"].items()})
    ref = cs.reference_logits(
        dataclasses.replace(cfg, num_layers=1), one, items)
    assert cs.logit_error(got, ref) > cs.LOGIT_REL_TOL
