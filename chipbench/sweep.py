"""Knee sweep of one cell: SLO attainment and backlog at a list of rates.

    python3 chipbench/sweep.py --workload <cell> --rates 1,2,3 \\
        --seconds <s> --seeds 1,2,3 [--out FILE]

One process builds the cell once (weights from the first seed) and serves
its traffic mix at each rate, on each seed's order of the traffic, through
a fresh engine on the same compiled backend.  For each rate and seed it
prints the share of latency requests due in the window that met both their
TTFT and gap limits, the backlog (requests sent and not finished) at the
window's start and end, the median request lifetime (due to last token, of
the requests that finished), and the end-to-end metrics.  The knee is the
highest rate at which at least 90% of latency requests meet both limits and
the backlog does not grow; the cell runs at about 0.8 of it.  Where no rate
meets the limits, it runs at 0.8 of the highest rate whose backlog does not
grow (``choose``).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from chipbench import run  # noqa: E402


def backlog(res, t: float) -> int:
    return sum(1 for r in res["records"].values()
               if r["due"] <= t and (r["finish"] is None or r["finish"] > t)
               and not r["shed"])


def attainment(res, slo) -> float:
    lat = run.in_window_latency(res)
    met = sum(slo.slo_met("latency", r["due"], r["token_times"],
                          r["finish"] is not None, ttft=r["ttft_limit"],
                          tbt=r["gap_limit"]) for r in lat)
    return met / len(lat) if lat else float("nan")


def choose(rows, seeds_min_preroll: float = 15.0) -> dict:
    """The rate to run the cell at, from the sweep's rows.  Per rate, the
    median over seeds of the attainment, of the backlog's growth over the
    window and of the median lifetime.  A backlog is flat where its median
    growth is at most 3 requests or a quarter of its median at the window's
    start.  Capacity: the highest rate whose backlog, and every lower
    rate's, is flat.  Knee: the highest such rate at which 90% of latency
    requests met both limits.  The cell runs at 0.8 of the knee, or of the
    capacity where no rate met the limits; its pre-roll is at least the
    median lifetime at that knee or capacity, in whole 5 s."""
    per = {}
    for r in rows:
        per.setdefault(r["rate"], []).append(r)
    summary, capacity, knee = [], None, None
    for rate in sorted(per):
        rs = per[rate]
        grow = statistics.median(r["backlog_end"] - r["backlog_start"]
                                 for r in rs)
        start = statistics.median(r["backlog_start"] for r in rs)
        att = statistics.median(r["attainment"] for r in rs)
        life = statistics.median(r["lifetime_p50"] or 0.0 for r in rs)
        flat = grow <= max(3.0, 0.25 * start)
        summary.append(dict(rate=rate, attainment=att, backlog_growth=grow,
                            backlog_start=start, lifetime_p50=life,
                            flat=flat))
        if not flat:
            break
        capacity = summary[-1]
        if att >= 0.9:
            knee = summary[-1]
    base = knee or capacity
    out = dict(per_rate=summary, capacity=capacity and capacity["rate"],
               knee=knee and knee["rate"])
    if base is not None:
        out.update(rate=round(0.8 * base["rate"], 3),
                   preroll_s=max(seeds_min_preroll,
                                 5.0 * math.ceil(base["lifetime_p50"] / 5)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from chipbench import serve as sv
    from chipbench import slo
    from chipbench import traffic as tr

    seeds = [int(x) for x in args.seeds.split(",")]
    s = run.build(args.workload, seeds[0], args.seconds)
    be, mix = s["be"], s["mix"]
    rows = []
    for rate in [float(x) for x in args.rates.split(",")]:
        for seed in seeds:
            m = dict(mix, rate=rate)
            arrs = tr.arrivals(m, m["preroll_s"] + args.seconds, seed)
            toks = tr.prompt_tokens(arrs, s["k"]["V"], seed)
            be.reset_run_state()
            res = sv.serve(be, arrs, toks, s["warm"], m["preroll_s"],
                           args.seconds)
            res.pop("engine")
            w0, w1 = res["window"]
            e2e = run.end_to_end(res, args.seconds, slo)
            run.report_counts(res, e2e, slo)
            life = sorted(r["finish"] - r["due"]
                          for r in res["records"].values() if r["finish"])
            row = dict(rate=rate, seed=seed,
                       attainment=attainment(res, slo),
                       lifetime_p50=life[len(life) // 2] if life else None,
                       backlog_start=backlog(res, w0),
                       backlog_end=backlog(res, w1),
                       shed=sum(r["shed"] for r in res["records"].values()),
                       compiles_in_window=res["compiles_in_window"],
                       **e2e["metrics"])
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = dict(workload=args.workload, seeds=seeds, seconds=args.seconds,
               preroll_s=mix["preroll_s"], pool_pages=s["pool"]["pages"],
               rows=rows, choice=choose(rows))
    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
