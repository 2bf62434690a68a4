"""Open-loop request traffic for the chip benchmark, generated from a mix file.

The length, arrival and SLO arithmetic is a frozen copy of the serving
program's workload generator (lognormal lengths matched to a (mean, median)
pair, Poisson or gamma-bursty arrivals, latency:throughput mix with a
best-effort share, per-user SLO jitter, noisy length hint), so a change to
the program cannot move the yardstick.

Every seed gets the same work: the lengths, classes, SLOs and arrival
instants of a run are drawn once from the mix's ``population_seed``; the
run's ``--seed`` only deals the requests to those instants in another order
(the requests due in the pre-roll among themselves, and those due in the
measured window among themselves, so that every seed's window holds the
same requests under the same bursts) and draws the prompt token ids.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

BURST_BLOCK = 16          # arrivals per re-drawn burst rate (BurstGPT-style)


@dataclasses.dataclass(frozen=True)
class Slo:
    kind: str                      # latency | throughput | none
    ttft: float = 2.0
    tbt: float = 0.1
    ttlt: float = 1e9


@dataclasses.dataclass(frozen=True)
class Arrival:
    due: float                     # s from the start of the pre-roll
    kind: str
    prompt_len: int
    output_len: int
    slo: Slo
    hint: float


def lognormal_from(mean: float, p50: float, rng: np.random.Generator,
                   n: int = 1) -> np.ndarray:
    """Lognormal matching the (mean, median) pair: mu = ln p50,
    sigma = sqrt(2 ln(mean/p50))."""
    mu = math.log(max(p50, 1.0))
    sigma = math.sqrt(max(2.0 * math.log(max(mean, 1.0) / max(p50, 1.0)),
                          0.05))
    return np.maximum(1, rng.lognormal(mu, sigma, n)).astype(int)


class MixGen:
    """The copied generator, parameterised by one traffic file."""

    def __init__(self, mix: dict, rng: np.random.Generator):
        self.mix = mix
        self.rng = rng

    def lens(self):
        p, o = self.mix["prompt"], self.mix["output"]
        li = int(lognormal_from(p["mean"], p["p50"], self.rng)[0])
        lo = int(lognormal_from(o["mean"], o["p50"], self.rng)[0])
        li = min(li, p["cap"])
        lo = min(lo, o["cap"])
        return max(li, p["min"]), max(lo, o["min"])

    def slo(self, kind: str) -> Slo:
        s = self.mix["slo"]
        f = s["scale"] * float(np.exp(self.rng.normal(0, s["jitter_sigma"])))
        if kind == "latency":
            return Slo("latency", ttft=s["ttft_s"] * f, tbt=s["gap_s"] * f)
        if kind == "throughput":
            return Slo("throughput", ttlt=s["ttlt_s"] * f)
        return Slo("none")

    def hint(self, out_len: int) -> float:
        return float(np.log1p(out_len)
                     + self.rng.normal(0, self.mix["hint_noise"]))

    def gaps(self, duration: float) -> List[float]:
        """Inter-arrival gaps until ``duration`` at the mix's rate."""
        rate0 = float(self.mix["rate"])
        bursty = self.mix["arrival"] == "bursty"
        gaps, t, rate = [], 0.0, rate0
        while t < duration:
            if bursty and len(gaps) % BURST_BLOCK == 0:
                # re-draw the short-term rate from a Gamma, floored so a
                # lull cannot stall the arrival stream
                rate = rate0 * float(self.rng.gamma(0.7, 1.0 / 0.7))
                rate = max(rate, 0.25 * rate0)
            g = float(self.rng.exponential(1.0 / rate))
            t += g
            gaps.append(g)
        return gaps

    def kind(self) -> str:
        m = self.mix["mix"]
        lat = m["latency"] / (m["latency"] + m["throughput"])
        u = self.rng.random()
        if self.rng.random() < self.mix["best_effort_frac"]:
            return "none"
        return "latency" if u < lat else "throughput"


def population(mix: dict, duration: float):
    """(gaps, [(kind, prompt_len, output_len, slo, hint)]) drawn from the
    mix's fixed population seed."""
    g = MixGen(mix, np.random.default_rng(mix["population_seed"]))
    gaps = g.gaps(duration)
    reqs = []
    for _ in gaps:
        kind = g.kind()
        li, lo = g.lens()
        reqs.append((kind, li, lo, g.slo(kind), g.hint(lo)))
    return gaps, reqs


def arrivals(mix: dict, duration: float, seed: int) -> List[Arrival]:
    """The run's arrivals: the population's arrival instants, with its
    requests dealt to them in an order drawn from ``seed`` within each of
    the pre-roll ``[0, preroll_s)``, the window ``[preroll_s, duration)``
    and what falls due after it."""
    gaps, reqs = population(mix, duration)
    due = np.cumsum(gaps)
    rng = np.random.default_rng(seed)
    cuts = np.searchsorted(due, [mix["preroll_s"], duration])
    order = np.concatenate([a + rng.permutation(b - a) for a, b in
                            zip([0, *cuts], [*cuts, len(reqs)])])
    return [Arrival(float(t), *reqs[i]) for t, i in zip(due, order)]


def prompt_tokens(arrs: List[Arrival], vocab: int, seed: int):
    """Prompt token ids per arrival, uniform over the vocabulary."""
    rng = np.random.default_rng((seed, 1))
    return [rng.integers(0, vocab, a.prompt_len, dtype=np.int32)
            for a in arrs]


def warmup_population(mix: dict, n: int = 512):
    """Completed-looking requests to warm-start the length predictor, from
    a dedicated stream (copy of the program's warm-up draw order: kinds
    cycle latency, throughput, collective)."""
    g = MixGen(mix, np.random.default_rng(mix["population_seed"] + 777_777))
    out = []
    for i in range(n):
        kind = ("latency", "throughput", "collective")[i % 3]
        li, lo = g.lens()
        out.append((kind, li, lo, g.slo(kind if kind != "collective"
                                        else "throughput"), g.hint(lo)))
    return out
