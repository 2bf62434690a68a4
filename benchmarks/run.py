"""Benchmark runner — one function per paper table/figure (see
benchmarks/paper_figs.py) plus the kernel micro-bench.  Prints
``bench,key=value,...`` CSV lines and persists JSON under
experiments/bench/.

  PYTHONPATH=src python -m benchmarks.run [--only fig13_load] [--full]
  PYTHONPATH=src python -m benchmarks.run --check      # CI regression gate
  PYTHONPATH=src python -m benchmarks.run --tp 2 ...   # jax benches on a
                                                       # 2-device mesh

--check reruns every bench with a committed baseline JSON under
experiments/bench/ and gates the fresh rows against it within tolerance
(benchmarks/check.py), plus the relational gmg >= tempo gate
(benchmarks/gmg.py) when gmg is in the run set.  Fresh JSONs are written
regardless, so CI can upload them as artifacts.
"""

from __future__ import annotations

import argparse
import inspect
import sys
import time


def _kernel_bench() -> list:
    """Interpret-mode per-call cost + analytic HBM traffic of the Pallas
    kernels (real TPU timings require hardware; the roofline table covers
    the perf model)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.paged_attention import paged_attention
    rng = np.random.default_rng(0)
    rows = []
    B, S, H, KV, D = 1, 512, 4, 4, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.float32)
    t0 = time.perf_counter()
    flash_attention(q, k, v, block_q=128, block_k=128,
                    interpret=True).block_until_ready()
    dt = time.perf_counter() - t0
    hbm = (q.size + k.size + v.size + q.size) * 4
    rows.append(dict(kernel="flash_attention", shape=f"B{B}S{S}H{H}D{D}",
                     interpret_ms=round(1e3 * dt, 1),
                     kernel_hbm_bytes=hbm,
                     xla_path_bytes_est=int(2 * B * H * S * S * 4 * 3)))
    page, P, nmax = 128, 16, 4
    q2 = jnp.asarray(rng.normal(size=(8, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    tb = jnp.asarray(rng.integers(0, P, size=(8, nmax)).astype(np.int32))
    cx = jnp.asarray(np.full(8, nmax * page, np.int32))
    t0 = time.perf_counter()
    paged_attention(q2, kp, vp, tb, cx, interpret=True).block_until_ready()
    rows.append(dict(kernel="paged_attention", shape=f"B8ctx{nmax*page}",
                     interpret_ms=round(1e3 * (time.perf_counter() - t0), 1),
                     kernel_hbm_bytes=int(8 * nmax * page * KV * D * 4 * 2)))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale durations (slower)")
    ap.add_argument("--check", action="store_true",
                    help="regression gate: run the benches that have "
                    "committed baselines and compare within tolerance")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel mesh degree for benches that "
                    "run the jax backend (needs >= tp local devices)")
    args = ap.parse_args()

    from repro.serving.run import enable_compile_cache
    enable_compile_cache()

    from benchmarks import check as checkmod
    from benchmarks.common import save
    from benchmarks.cluster_sweep import ALL as CLUSTER
    from benchmarks.decode_speed import ALL as DECODE_SPEED
    from benchmarks.fleet_sweep import ALL as FLEET
    from benchmarks.gmg import ALL as GMG
    from benchmarks.paper_figs import ALL
    from benchmarks.prefix_reuse import ALL as PREFIX
    from benchmarks.spec_decode import ALL as SPEC

    benches = dict(ALL)
    benches.update(CLUSTER)
    benches.update(PREFIX)
    benches.update(GMG)
    benches.update(DECODE_SPEED)
    benches.update(SPEC)
    benches.update(FLEET)
    benches["kernels"] = lambda quick=True: _kernel_bench()
    names = [n for n in benches if (not args.only or args.only in n)]
    baselines = {}
    if args.check and args.tp > 1:
        # tp>1 tags jax rows with a 'tp' identity key, so they can never
        # match the committed (tp=1) baselines — and the run would
        # overwrite those baselines on disk before failing
        ap.error("--check compares against the committed tp=1 baselines; "
                 "run --tp sweeps without --check")
    if args.check:
        # gate scope: benches with a committed baseline (∩ --only filter);
        # snapshot the baselines NOW — save() below overwrites the files
        # with fresh rows (which CI uploads as artifacts)
        with_baseline = set(checkmod.baseline_names())
        names = [n for n in names if n in with_baseline]
        if not names:
            print("check: no benches with committed baselines matched")
            sys.exit(1)
        baselines = {n: checkmod.load_baseline(n) for n in names}

    t_all = time.time()
    fresh = {}
    for name in names:
        t0 = time.time()
        fn = benches[name]
        kw = {"quick": not args.full}
        if args.tp > 1 and "tp" in inspect.signature(fn).parameters:
            kw["tp"] = args.tp
        try:
            rows = fn(**kw)
        except Exception as e:  # pragma: no cover
            print(f"{name},ERROR,{e!r}", flush=True)
            raise
        fresh[name] = rows
        save(name, rows)
        for r in rows:
            kv = ",".join(f"{k}={v}" for k, v in r.items()
                          if not isinstance(v, (list, dict)))
            print(f"{name},{kv}", flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    print(f"# all benchmarks done in {time.time()-t_all:.1f}s", flush=True)

    if args.check:
        code = checkmod.check_all(fresh, baselines)
        if "gmg" in fresh:
            from benchmarks.gmg import check as gmg_check
            code = gmg_check(fresh["gmg"]) or code
        if "decode_speed" in fresh:
            from benchmarks.decode_speed import check as ds_check
            code = ds_check(fresh["decode_speed"]) or code
        if "spec_decode" in fresh:
            from benchmarks.spec_decode import check as spec_check
            code = spec_check(fresh["spec_decode"]) or code
        if "disagg" in fresh:
            from benchmarks.cluster_sweep import disagg_check
            code = disagg_check(fresh["disagg"]) or code
        if "fleet_profile" in fresh or "fleet_sweep" in fresh:
            from benchmarks.fleet_sweep import fleet_check
            code = fleet_check(fresh.get("fleet_sweep", [])
                               + fresh.get("fleet_profile", [])) or code
        sys.exit(code)


if __name__ == "__main__":
    main()
