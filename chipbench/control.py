"""Readings that the output check's limit is set from, in one process.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s> [--control-seeds 1,2,3] [--out FILE]

For each seed: the seed's weights and traffic, served through the same
compiled program for a short window at the cell's own rate, then the
check's number on the served tokens (``max_logit_gap``: the widest gap by
which a served token's float32 reference logit lies below the reference's
best).  For the control seeds also the control's reading: the same gap for
the token that the reference computed in float8 (the nearest precision
below the configuration's bfloat16) puts first, at every position of the
same prompts and served tokens.  The limit goes between the largest
program reading and the smallest control reading.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from chipbench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from chipbench import serve as sv
    from chipbench import traffic as tr

    seeds = [int(x) for x in args.seeds.split(",")]
    ctrl_seeds = {int(x) for x in args.control_seeds.split(",") if x}
    s = run.build(args.workload, seeds[0], args.seconds)
    be, ref, cfg, mix = s["be"], s["ref"], s["cfg"], s["mix"]
    rows = []
    for seed in seeds:
        if seed != seeds[0]:
            be.params = s["weights"] = None
            gc.collect()
            key = jax.random.PRNGKey(
                np.random.default_rng(seed).integers(2**31))
            s["weights"] = be.params = ref.make_weights(cfg, key)
            s["arrs"] = tr.arrivals(mix, mix["preroll_s"] + args.seconds,
                                    seed)
            s["tokens"] = tr.prompt_tokens(s["arrs"], s["k"]["V"], seed)
        be.reset_run_state()
        res = sv.serve(be, s["arrs"], s["tokens"], s["warm"],
                       mix["preroll_s"], args.seconds)
        res.pop("engine")
        readings, ctrl = run.check(res, s["tokens"], cfg, s["weights"], ref,
                                   mix["compare_requests"], seed,
                                   control=seed in ctrl_seeds)
        row = dict(seed=seed, **readings, control_gap=ctrl,
                   finished=sum(r["finish"] is not None
                                for r in res["records"].values()))
        rows.append(row)
        print(json.dumps(row), flush=True)
    prog = max(r["max_logit_gap"] for r in rows)
    ctl = [r["control_gap"] for r in rows if r["control_gap"] is not None]
    summary = dict(workload=args.workload, seconds=args.seconds,
                   lower=prog, upper=min(ctl) if ctl else None, rows=rows)
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
