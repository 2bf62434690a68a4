"""The benchmark's frozen traffic generator and SLO arithmetic, on their
own (no comparison with the program's copy, which may change)."""

import json
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from chipbench import slo, traffic  # noqa: E402


def _mix(name):
    with open(os.path.join(ROOT, "chipbench", "traffic", name + ".json")) as f:
        return json.load(f)


def test_lognormal_matches_table2_mean_and_median():
    rng = np.random.default_rng(0)
    x = traffic.lognormal_from(318, 225, rng, 400_000)
    assert abs(np.median(x) - 225) / 225 < 0.02
    assert abs(x.mean() - 318) / 318 < 0.02
    x = traffic.lognormal_from(93, 27, rng, 400_000)
    assert abs(np.median(x) - 27) <= 1
    assert abs(x.mean() - 93) / 93 < 0.05


@pytest.mark.parametrize("name", ["chat", "longdoc"])
def test_mix_shares_and_caps(name):
    mix = dict(_mix(name), rate=50.0)
    _, reqs = traffic.population(mix, 400.0)
    kinds = [k for k, *_ in reqs]
    n = len(kinds)
    be = kinds.count("none") / n
    assert abs(be - mix["best_effort_frac"]) < 0.01
    lat = kinds.count("latency") / max(1, n - kinds.count("none"))
    want = mix["mix"]["latency"] / (mix["mix"]["latency"]
                                    + mix["mix"]["throughput"])
    assert abs(lat - want) < 0.02
    for _, li, lo, s, _ in reqs:
        assert mix["prompt"]["min"] <= li <= mix["prompt"]["cap"]
        assert mix["output"]["min"] <= lo <= mix["output"]["cap"]
        if s.kind == "latency":
            assert s.ttft / s.tbt == pytest.approx(20.0)


def test_rate_of_poisson_population():
    mix = dict(_mix("chat"), rate=4.0)
    gaps, _ = traffic.population(mix, 2000.0)
    assert abs(len(gaps) / 2000.0 - 4.0) < 0.15


def test_every_seed_gets_the_same_work():
    """A seed reorders the population's requests over its arrival
    instants; the multiset of lengths, classes and gaps (and so the total
    span) is the same for every seed."""
    mix = _mix("longdoc")
    a = traffic.arrivals(mix, 60.0, 1)
    b = traffic.arrivals(mix, 60.0, 2**31 + 12345)
    key = lambda x: sorted((r.kind, r.prompt_len, r.output_len) for r in x)
    assert key(a) == key(b)
    assert a[-1].due == pytest.approx(b[-1].due)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    da = np.diff([0.0] + [r.due for r in a])
    db = np.diff([0.0] + [r.due for r in b])
    assert sorted(np.round(da, 9)) == sorted(np.round(db, 9))


def test_bursts_survive_the_reorder():
    """The arrival instants are the population's, so each block of 16
    gaps keeps the one burst rate it was drawn at."""
    mix = dict(_mix("longdoc"), rate=2.0)
    gaps, _ = traffic.population(mix, 300.0)
    arr = traffic.arrivals(mix, 300.0, 7)
    g = np.diff([0.0] + [r.due for r in arr])
    blocks = {tuple(sorted(np.round(gaps[i:i + 16], 6)))
              for i in range(0, len(gaps), 16)}
    got = {tuple(sorted(np.round(g[i:i + 16], 6)))
           for i in range(0, len(g) - len(g) % 16, 16)}
    assert got <= blocks


def test_every_seed_gets_the_same_window():
    """The requests due in the pre-roll, and those due in the window, are
    the same for every seed, in another order."""
    mix = dict(_mix("longdoc"), preroll_s=20.0)
    a = traffic.arrivals(mix, 71.0, 5)
    b = traffic.arrivals(mix, 71.0, 2**31 + 77)
    for lo, hi in ((0.0, 20.0), (20.0, 71.0), (71.0, math.inf)):
        seg = lambda x: [(r.kind, r.prompt_len, r.output_len, r.slo)
                         for r in x if lo <= r.due < hi]
        assert sorted(seg(a), key=repr) == sorted(seg(b), key=repr)
        assert [r.due for r in a if lo <= r.due < hi] == \
            [r.due for r in b if lo <= r.due < hi]
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]


def test_prompt_tokens_seeded_and_in_vocab():
    arr = traffic.arrivals(_mix("chat"), 20.0, 3)
    t1 = traffic.prompt_tokens(arr, 1000, 3)
    t2 = traffic.prompt_tokens(arr, 1000, 3)
    assert all(np.array_equal(x, y) for x, y in zip(t1, t2))
    assert all(len(t) == r.prompt_len for t, r in zip(t1, arr))
    assert max(int(t.max()) for t in t1) < 1000


def test_warmup_population_is_fixed():
    mix = _mix("chat")
    assert traffic.warmup_population(mix, 30) == \
        traffic.warmup_population(mix, 30)


# -- the predicate on hand-built stamps ------------------------------------
def test_latency_predicate():
    ok = [1.0, 1.05, 1.1, 1.15]
    assert slo.slo_met("latency", 0.0, ok, True, ttft=2.0, tbt=0.1)
    assert not slo.slo_met("latency", 0.0, ok, True, ttft=0.5, tbt=0.1)
    assert not slo.slo_met("latency", 0.0, ok, False, ttft=2.0, tbt=0.1)
    slow = [1.0] + [1.0 + 0.2 * i for i in range(1, 30)]
    assert not slo.slo_met("latency", 0.0, slow, True, ttft=2.0, tbt=0.1)
    # the request's own 95th-percentile gap decides, not its worst gap
    one_stall = [0.05 * i for i in range(1, 41)]
    one_stall[-1] += 1.0
    assert slo.slo_met("latency", 0.0, one_stall, True, ttft=2.0, tbt=0.1)


def test_multi_step_burst():
    """Four tokens delivered together share one stamp: three gaps of 0 and
    one long pause; over a short stream the pause sets the 95th
    percentile."""
    burst = [1.0, 1.0, 1.0, 1.0, 1.4, 1.4, 1.4, 1.4]
    assert not slo.slo_met("latency", 0.0, burst, True, ttft=2.0, tbt=0.3)
    assert slo.slo_met("latency", 0.0, burst, True, ttft=2.0, tbt=0.4)


def test_throughput_and_best_effort_predicate():
    assert slo.slo_met("throughput", 1.0, [5.0, 20.9], True, ttlt=20.0)
    assert not slo.slo_met("throughput", 1.0, [5.0, 21.1], True, ttlt=20.0)
    assert not slo.slo_met("throughput", 1.0, [5.0], False, ttlt=20.0)
    assert slo.slo_met("none", 0.0, [99.0], True)
    assert not slo.slo_met("none", 0.0, [], False)


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert slo.percentile(xs, 90) == 90
    assert slo.percentile(xs, 95) == 95
    assert slo.percentile([3.0], 90) == 3.0
    assert slo.percentile([], 90) is None


def test_goodput_counts_finished_in_window_and_met():
    base = dict(kind="latency", ttft_limit=2.0, gap_limit=0.1, ttlt_limit=1e9)
    recs = [
        dict(base, due=0.0, token_times=[1.0, 1.05], finish=1.05,
             output_len=2),                                   # met, in
        dict(base, due=0.0, token_times=[3.0, 3.05], finish=3.05,
             output_len=2),                                   # late TTFT
        dict(base, due=5.0, token_times=[5.5, 5.6], finish=11.0,
             output_len=2),                                   # after window
        dict(base, due=0.0, token_times=[0.5], finish=None,
             output_len=9),                                   # unfinished
    ]
    assert slo.goodput_tokens(recs, 0.0, 10.0) == 2
    assert math.isclose(slo.goodput_tokens(recs, 0.0, 12.0), 4)
