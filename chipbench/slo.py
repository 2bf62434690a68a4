"""SLO attainment and goodput on wall-clock stamps.

``slo_met`` is a frozen copy of the serving program's binary goodput
predicate: a latency request meets its SLO when its TTFT is within the
limit and the 95th percentile of its own token gaps is within the gap
limit; a throughput request when its last token is within its TTLT limit;
a best-effort request when it finishes.  Here it is applied to the
benchmark's own stamps: the due time, and one stamp per token at the wall
time the step that produced it returned (tokens that arrive together share
a stamp).
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence


def slo_met(kind: str, due: float, token_times: Sequence[float],
            finished: bool, *, ttft: float = 2.0, tbt: float = 0.1,
            ttlt: float = 1e9, tbt_pctl: float = 0.95) -> bool:
    if kind == "none":
        return finished
    if not finished:
        return False
    if kind == "latency":
        if not token_times or token_times[0] - due > ttft:
            return False
        tbts = sorted(b - a for a, b in zip(token_times, token_times[1:]))
        if not tbts:
            return True
        k = min(len(tbts) - 1, int(tbt_pctl * len(tbts)))
        return tbts[k] <= tbt
    return (token_times[-1] - due) <= ttlt


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in [0, 100]); None for no samples."""
    if not xs:
        return None
    s = sorted(xs)
    k = max(0, min(len(s) - 1, math.ceil(q / 100.0 * len(s)) - 1))
    return float(s[k])


def goodput_tokens(reqs: List[dict], w0: float, w1: float) -> int:
    """Output tokens of the requests that finished inside [w0, w1] and met
    their SLO.  ``reqs`` are the harness's records (see ``serve.Record``)."""
    n = 0
    for r in reqs:
        fin = r["finish"]
        if fin is None or not (w0 <= fin <= w1):
            continue
        if slo_met(r["kind"], r["due"], r["token_times"], True,
                   ttft=r["ttft_limit"], tbt=r["gap_limit"],
                   ttlt=r["ttlt_limit"]):
            n += r["output_len"]
    return n
