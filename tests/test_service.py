"""Service-gain model (§3.1): Eq. 1–3 and the degradation function."""

import math

import pytest
from hypothesis import given, strategies as st

from repro.core.service import ServiceModel
from repro.serving.request import Request, SLOSpec


def _req(kind="throughput", li=100, lo=50, **slo):
    return Request(rid=1, app="code", arrival=0.0, prompt_len=li,
                   true_output_len=lo, slo=SLOSpec(kind, **slo))


def test_degrade_within_slo_is_one():
    sm = ServiceModel()
    assert sm.degrade(10.0, 5.0) == 1.0
    assert sm.degrade(10.0, 10.0) == 1.0


def test_degrade_divisive_decay():
    sm = ServiceModel(alpha=1.0)
    assert sm.degrade(10.0, 20.0) == pytest.approx(0.5)
    sm2 = ServiceModel(alpha=2.0)
    assert sm2.degrade(10.0, 20.0) == pytest.approx(0.25)


def test_alpha_inf_recovers_goodput():
    sm = ServiceModel(alpha=math.inf)
    assert sm.degrade(10.0, 10.0) == 1.0
    assert sm.degrade(10.0, 10.01) == 0.0


@given(slo=st.floats(0.1, 100), metric=st.floats(0.01, 1000),
       alpha=st.floats(0.1, 8))
def test_degrade_bounds_and_monotonicity(slo, metric, alpha):
    sm = ServiceModel(alpha=alpha)
    f = sm.degrade(slo, metric)
    assert 0.0 <= f <= 1.0
    # monotone non-increasing in the metric
    assert sm.degrade(slo, metric * 1.5) <= f + 1e-12


def test_eq2_throughput_gain():
    sm = ServiceModel()
    r = _req(ttlt=20.0)
    r.finish_t = 10.0          # within deadline
    r.decoded = r.true_output_len
    assert sm.realized_gain(r) == pytest.approx(1 * 100 + 2 * 50)
    r.finish_t = 40.0          # 2x late -> half gain
    assert sm.realized_gain(r) == pytest.approx(200 * 0.5)


def test_eq3_latency_per_token():
    sm = ServiceModel()
    r = _req(kind="latency", li=10, lo=3, ttft=1.0, tbt=0.1)
    r.first_token_t = 0.5
    r.token_times = [0.5, 0.58, 0.9]   # second gap 0.08 ok, third 0.32 late
    r.decoded = 3
    r.finish_t = 0.9
    g = sm.realized_gain(r)
    expected = 1 * 10 * 1.0 + 2 * 1.0 + 2 * (0.1 / 0.32) + 2  # ttft+tok2+tok3... order
    # tokens: gaps [0.08, 0.32] -> f=1 and f=0.3125; +w_o for first token
    expected = 10 * 1.0 + 2 * 1.0 + 2 * 0.3125 + 2.0
    assert g == pytest.approx(expected)


def test_gain_bounded_by_max():
    sm = ServiceModel()
    r = _req(ttlt=20.0)
    r.finish_t = 5.0
    assert sm.realized_gain(r) <= sm.max_gain(r) + 1e-9


def test_slo_met_latency_p95():
    sm = ServiceModel()
    r = _req(kind="latency", ttft=1.0, tbt=0.1)
    r.first_token_t = 0.5
    r.token_times = [0.5 + 0.05 * i for i in range(20)]
    r.finish_t = r.token_times[-1]
    assert sm.slo_met(r)
    r.token_times[10] = r.token_times[9] + 5.0   # one huge gap
    r.token_times = sorted(r.token_times)
    assert not sm.slo_met(r)
