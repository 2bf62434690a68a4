"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell is a model configuration (``chipbench/configs/<config>.json``, with
its plain reference in ``chipbench/references/``) under a traffic mix
(``chipbench/traffic/<mix>.json``); per-layer metrics are read by
``chipbench/metrics/<metric>.py``, and the limits of the output check by
cell from ``chipbench/cells/<cell>.json``.  Nothing here names a cell.

The run builds seeded bf16 weights on the device, sizes the page pool from
the compiled steps, warms every dispatch shape the traffic can reach (all of
that is ``setup_s``), serves the traffic open-loop on the wall clock through
a pre-roll and then the measured window, and finally checks a seeded sample
of the finished requests against the float32 reference.  ``--trace 1``
records the window with the JAX profiler and reports the per-layer metrics
instead of the end-to-end ones.  The last line of standard output is one
JSON object; without an accelerator, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                # noqa: E402
import gc                                                      # noqa: E402
import importlib.util                                          # noqa: E402
import json                                                    # noqa: E402
import os                                                      # noqa: E402
import shutil                                                  # noqa: E402
import statistics                                              # noqa: E402
import sys                                                     # noqa: E402
import tempfile                                                # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

HARNESS_SPANS = ("engine.", "sched.", "backend.", "harness.")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(name: str, root: str = ROOT):
    """(BENCHMARK.json, cell entry, configuration, traffic mix, limits)."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    here = os.path.join(root, "chipbench")
    cfg = load_json(here, "configs", w["config"] + ".json")
    mix = load_json(here, "traffic", w["traffic"] + ".json")
    limits = load_json(here, "cells", name + ".json")
    return bench, w, cfg, mix, limits


def reference(cfg: dict, root: str = ROOT):
    return load_module(os.path.join(root, "chipbench", "references",
                                    cfg["reference"] + ".py"),
                       "chipbench_ref_" + cfg["reference"])


def say(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------
def device_info(chips: int, rehearse: bool):
    """The devices JAX found, refused unless they are accelerators known to
    ``peaks.json`` and at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    d = devs[0]
    peaks = load_json(HERE, "peaks.json")
    if not rehearse:
        if d.platform == "cpu":
            raise SystemExit("chipbench: JAX found no accelerator "
                             f"(platform {d.platform!r}); refusing to run")
        if len(devs) < chips:
            raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                             f"sees {len(devs)}")
        if d.device_kind not in peaks:
            raise SystemExit(f"chipbench: no peaks for device kind "
                             f"{d.device_kind!r} in peaks.json")
    return d, dict(platform=d.platform, kind=d.device_kind, count=len(devs)), \
        peaks.get(d.device_kind)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def in_window_latency(res):
    w0, w1 = res["window"]
    return [r for r in res["records"].values()
            if r["kind"] == "latency" and w0 <= r["due"] < w1]


def end_to_end(res, seconds: float, slo) -> dict:
    w0, w1 = res["window"]
    recs = list(res["records"].values())
    ttft = [(min(r["deliveries"][0][0], w1) if r["deliveries"] else w1)
            - r["due"] for r in in_window_latency(res)]
    gaps = []
    for r in in_window_latency(res):
        ts = [t for t, _ in r["deliveries"] if t <= w1]
        gaps += [b - a for a, b in zip(ts, ts[1:])]
    out_tok = sum(k for r in recs for t, k in r["deliveries"]
                  if w0 <= t <= w1)
    good = slo.goodput_tokens(recs, w0, w1)
    return dict(ttft=ttft, gaps=gaps, metrics=dict(
        ttft_p90_s=slo.percentile(ttft, 90),
        stream_gap_p95_s=slo.percentile(gaps, 95),
        goodput_tok_s=good / seconds,
        output_tok_s=out_tok / seconds))


def report_counts(res, e2e, slo) -> None:
    """Counts and medians printed for the reader; none is a metric."""
    w0, w1 = res["window"]
    recs = list(res["records"].values())
    due = [r for r in recs if w0 <= r["due"] < w1]
    att = {}
    for kind in ("latency", "throughput", "none"):
        rs = [r for r in due if r["kind"] == kind]
        met = sum(slo.slo_met(kind, r["due"], r["token_times"],
                              r["finish"] is not None,
                              ttft=r["ttft_limit"], tbt=r["gap_limit"],
                              ttlt=r["ttlt_limit"]) for r in rs)
        att[kind] = f"{met}/{len(rs)}"
    late = sorted(res["late"])
    say(f"window [{w0!r}, {w1!r}] s; sent {res['sent']} (due in window "
        f"{len(due)}), finished {sum(r['finish'] is not None for r in recs)}"
        f", shed {sum(r['shed'] for r in recs)}, failed 0; attainment "
        f"(met/due in window) {att}")
    med = {k: statistics.median(e2e[k]) if e2e[k] else None
           for k in ("ttft", "gaps")}
    say(f"ttft median {med['ttft']!r} s over {len(e2e['ttft'])} latency "
        f"requests; gap median {med['gaps']!r} s over "
        f"{len(e2e['gaps'])} gaps; generator lateness p50 "
        f"{late[len(late) // 2] if late else None!r} s, max "
        f"{late[-1] if late else None!r} s (arrivals are enqueued between "
        f"steps); steps {len(res['steps'])}; compiles or cache loads in the "
        f"window {res['compiles_in_window']}")


def per_layer(bench, cell_name, ctx) -> dict:
    out = {}
    for m in bench["per_layer"]:
        if cell_name not in m.get("workloads", [cell_name]):
            continue
        mod = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                          "chipbench_metric_" + m["name"].replace(".", "_"))
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = dict(value=v, unit=m["unit"])
    return out


# ---------------------------------------------------------------------------
# the output check
# ---------------------------------------------------------------------------
def sample(res, n: int, seed: int):
    """Up to ``n`` finished requests drawn from the seed, the longest
    (prompt plus output) among them."""
    import numpy as np
    fin = sorted(rid for rid, r in res["records"].items()
                 if r["finish"] is not None)
    if not fin:
        return []
    recs = res["records"]
    longest = max(fin, key=lambda i: (recs[i]["prompt_len"]
                                      + recs[i]["output_len"], i))
    rest = [i for i in fin if i != longest]
    rng = np.random.default_rng((seed, 2))
    pick = list(rng.choice(rest, size=min(n - 1, len(rest)), replace=False))
    return [longest] + [int(i) for i in pick]


def check(res, tokens, cfg, weights, ref, n: int, seed: int,
          control: bool = False):
    """Compare the sampled requests' served tokens with the reference.
    Returns (readings {name: value}, control gaps or None)."""
    import numpy as np
    rids = sample(res, n, seed)
    gaps, ctrl, short, served = [], [], 0, 0
    for rid in rids:
        rec = res["records"][rid]
        got = res["generated"][rid]
        short += rec["output_len"] - len(got)
        if not got:
            continue
        served += len(got)
        g, c = ref.gaps(cfg, weights, tokens[rid - 1], got,
                        fp8_control=control)
        gaps.append(g)
        if c is not None:
            ctrl.append(c)
    gap = float(np.max(np.concatenate(gaps))) if gaps else float("inf")
    readings = dict(max_logit_gap=gap, tokens_missing=short,
                    requests_checked=len(rids), tokens_checked=served)
    return readings, (float(np.max(np.concatenate(ctrl))) if ctrl else None)


def correct_of(readings, limits) -> dict:
    """{name: (value, limit)} of every number compared."""
    return {"max_logit_gap": (readings["max_logit_gap"],
                              limits["max_logit_gap"]),
            "tokens_missing": (readings["tokens_missing"], 0),
            "requests_checked": (readings["requests_checked"],
                                 "at least 1")}


def is_correct(cmp) -> bool:
    v, lim = cmp["max_logit_gap"]
    return (v <= lim and cmp["tokens_missing"][0] == 0
            and cmp["requests_checked"][0] >= 1)


# ---------------------------------------------------------------------------
def build(cell_name: str, seed: int, seconds: float, *, rehearse=None):
    """Set-up: everything before the pre-roll.  ``rehearse`` (tests only) is
    a dict {"config": cfg, "pool_pages": n, "mix": {...}} that replaces the
    configuration, the pool sizing and some of the mix's keys for a run on
    the CPU at a tiny size; such a run prints no metric."""
    import jax
    import numpy as np
    from chipbench import serve as sv
    from chipbench import traffic as tr

    bench, w, cfg, mix, limits = find_cell(cell_name)
    if rehearse:
        cfg = rehearse["config"]
        mix = dict(mix, **rehearse.get("mix", {}))
    dev, device, peak = device_info(w["chips"], bool(rehearse))
    sv.set_compile_cache(ROOT)
    ref = reference(cfg)
    k = ref.dims(cfg)
    max_len = min(cfg["max_position_embeddings"],
                  mix["prompt"]["cap"] + mix["output"]["cap"])
    key = jax.random.PRNGKey(np.random.default_rng(seed).integers(2**31))
    weights = ref.make_weights(cfg, key)
    max_batch, budget = sv.engine_defaults()
    groups = sv.prefill_groups(mix["prompt"]["cap"], budget, max_batch)
    widths = sv.decode_widths(max_batch)
    if rehearse:
        pool = dict(pages=rehearse["pool_pages"])
    else:
        pool = sv.pool_pages(cfg, weights, max_len, groups, max_batch, dev,
                             seed)
    say(f"pool {pool}")
    be = sv._backend(cfg, weights, pool["pages"], max_len, seed)
    sv.warm_up(be, groups, widths)
    say(f"warmed; device memory {dev.memory_stats()}")
    arrs = tr.arrivals(mix, mix["preroll_s"] + seconds, seed)
    tokens = tr.prompt_tokens(arrs, k["V"], seed)
    warm = tr.warmup_population(mix)
    jax.block_until_ready(be.pages)
    return dict(bench=bench, cell=w, cfg=cfg, mix=mix, limits=limits,
                dev=dev, device=device, peak=peak, ref=ref, k=k,
                weights=weights, be=be, arrs=arrs, tokens=tokens, warm=warm,
                pool=pool, max_len=max_len, groups=groups, widths=widths)


def release(s, res) -> None:
    """Free the program's device state before the reference runs."""
    res.pop("engine", None)
    be = s.pop("be")
    be.pages = be.params = None
    del be
    gc.collect()


def main(argv=None, *, rehearse=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench import serve as sv
    from chipbench import slo
    from chipbench import trace_reduce as trr

    s = build(args.workload, args.seed, args.seconds, rehearse=rehearse)
    setup_s = time.perf_counter() - T_START
    say(f"set-up {setup_s!r} s: {s['cfg']['name']} on {s['device']}; pool "
        f"{s['pool']['pages']} pages of {sv.PAGE} tokens ({s['pool']}); "
        f"max_len {s['max_len']}; {len(s['groups'])} prefill shapes, "
        f"{len(s['widths'])} decode widths warmed")
    mix = s["mix"]
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace else None
    res = sv.serve(s["be"], s["arrs"], s["tokens"], s["warm"],
                   mix["preroll_s"], args.seconds, trace_dir=tdir)
    peak_mem = (s["dev"].memory_stats() or {}).get("peak_bytes_in_use")
    device = dict(s["device"], memory_peak_bytes=peak_mem)
    e2e = end_to_end(res, args.seconds, slo)
    report_counts(res, e2e, slo)
    say(f"end to end (metrics only where BENCHMARK.json lists them for the "
        f"cell): {e2e['metrics']}")
    out = dict(correct=None, attempted=res["sent"], failed=0)
    if args.trace:
        tr_ = trr.load(trr.find_xplane(tdir), HARNESS_SPANS)
        hs = [e for e, _ in tr_.host]
        red = trr.reduce(tr_, min(e.start for e in hs), max(e.end for e in hs))
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = dict(k=s["k"], peak=s["peak"], steps=res["steps"],
                   window=res["window"], traced=res["traced"],
                   records=res["records"], trace=red)
        metrics = per_layer(s["bench"], args.workload, ctx)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = dict(device_ops=red["device_ops"],
                         idle_gaps=red["idle_gaps"])
    else:
        got = dict(e2e["metrics"], setup_s=setup_s)
        metrics = {m["name"]: dict(value=got[m["name"]], unit=m["unit"])
                   for m in s["bench"]["end_to_end"]
                   if args.workload in m.get("workloads", [args.workload])}
        breakdown = None
    tokens, cfg, weights, ref = s["tokens"], s["cfg"], s["weights"], s["ref"]
    release(s, res)
    t_ref = time.perf_counter()
    readings, _ = check(res, tokens, cfg, weights, ref,
                        mix["compare_requests"], args.seed)
    cmp = correct_of(readings, s["limits"])
    say(f"reference check: {readings} in {time.perf_counter() - t_ref!r} s")
    out["correct"] = is_correct(cmp)
    out["metrics"] = {} if rehearse else metrics
    out["device"] = device
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {n: {"value": v, "limit": lim}
                      for n, (v, lim) in cmp.items()}
    for n, (v, lim) in cmp.items():
        say(f"compared {n}: {v!r} (limit {lim})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
