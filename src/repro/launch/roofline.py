"""Roofline analysis from compiled (optimized, post-SPMD) HLO text.

XLA's ``compiled.cost_analysis()`` visits while-loop bodies ONCE, so a model
whose 61 layers run under `lax.scan` under-reports FLOPs by ~61x (verified
empirically — see EXPERIMENTS.md §Roofline notes).  This module therefore
walks the HLO text itself:

  * parses every computation and per-op result/operand shapes;
  * recovers `while` trip counts from the loop-condition's integer constant
    (all our scans are statically bounded) and multiplies through, including
    nested loops (unit scan × attention kv scan);
  * counts dot FLOPs (2·|result|·|contracted dims|), including dots inside
    fusions;
  * counts bytes accessed per materialized (top-level) op: result + operands
    — fusion internals excluded, mirroring HBM traffic;
  * sums collective bytes-on-wire per chip with standard ring factors.

The compiled module is the PER-DEVICE program, so all numbers are per chip.

Hardware constants (TPU v5e class, per assignment):
  197 TFLOP/s bf16,  819 GB/s HBM,  50 GB/s/link ICI.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Tuple

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

# measured-utilization denominators by ``jax.Device.device_kind``: a time
# measured on a device missing here gets no ``mfu_measured``
PEAK_FLOPS_BY_KIND = {"TPU v5 lite": PEAK_FLOPS}

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3b11fnuz": 1, "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes_and_dims(type_str: str) -> Tuple[float, List[List[int]]]:
    """Total bytes and list of dim-lists for (possibly tuple) type string."""
    total = 0.0
    dims_all = []
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        shape = [int(x) for x in dims.split(",") if x] if dims else []
        n = 1
        for s in shape:
            n *= s
        total += n * _DTYPE_BYTES[dt]
        dims_all.append(shape)
    return total, dims_all


_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^()]*\)|[a-z0-9]+\[[0-9,]*\]"
    r"(?:\{[^}]*\})?)\s+([\w\-]+)\(([^)]*(?:\([^)]*\))?[^)]*)\)(.*)$")

_COMP_HDR_RE = re.compile(
    r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->\s*.+\{\s*$")


class _Op:
    __slots__ = ("name", "type", "opcode", "operands", "attrs", "raw")

    def __init__(self, name, type_, opcode, operands, attrs, raw=""):
        self.name, self.type, self.opcode = name, type_, opcode
        self.operands, self.attrs, self.raw = operands, attrs, raw


def parse_hlo(text: str):
    """-> (computations: {name: [Op]}, entry_name, shapes: {(comp,op): type})"""
    comps: Dict[str, List[_Op]] = {}
    shapes: Dict[str, Dict[str, str]] = {}
    entry = None
    cur = None
    for line in text.splitlines():
        if cur is None:
            if line.rstrip().endswith("{"):
                m = _COMP_HDR_RE.match(line)
                if m:
                    cur = m.group(1)
                    comps[cur] = []
                    shapes[cur] = {}
                    if line.lstrip().startswith("ENTRY"):
                        entry = cur
                    # parameters from header (types may be tuples)
                    for pm in re.finditer(
                            r"%?([\w.\-]+):\s*(\([^()]*\)|[a-z0-9]+"
                            r"\[[0-9,]*\](?:\{[^}]*\})?)", m.group(2)):
                        shapes[cur][pm.group(1)] = pm.group(2)
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _OP_RE.match(line)
        if not m:
            # parameter declarations inside body: "%p = bf16[..] parameter(0)"
            continue
        name, type_, opcode, operands_s, attrs = m.groups()
        operands = re.findall(r"%([\w.\-]+)", operands_s)
        op = _Op(name, type_, opcode, operands, attrs, raw=line)
        comps[cur].append(op)
        shapes[cur][name] = type_
    return comps, entry, shapes


def _trip_count(comps, shapes, cond_name: str) -> int:
    """Max integer constant in the condition computation (jax scans count
    from 0 to a constant with LT)."""
    best = 1
    for op in comps.get(cond_name, []):
        for m in re.finditer(r"constant\((\d+)\)", op.raw):
            best = max(best, int(m.group(1)))
        cm = re.search(r"calls=%?([\w.\-]+)", op.attrs)
        if cm and cm.group(1) in comps:
            for op2 in comps[cm.group(1)]:
                for m in re.finditer(r"constant\((\d+)\)", op2.raw):
                    best = max(best, int(m.group(1)))
    return best


_COLL_FACTORS = {
    "all-reduce": lambda b, n: 2.0 * b * (n - 1) / max(n, 1),
    "all-reduce-start": lambda b, n: 2.0 * b * (n - 1) / max(n, 1),
    "all-gather": lambda b, n: b * (n - 1) / max(n, 1),
    "all-gather-start": lambda b, n: b * (n - 1) / max(n, 1),
    "reduce-scatter": lambda b, n: b * (n - 1),
    "all-to-all": lambda b, n: b * (n - 1) / max(n, 1),
    "collective-permute": lambda b, n: b,
    "collective-permute-start": lambda b, n: b,
}

_SKIP_BYTES = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "while", "after-all", "iota"}


def _group_size(attrs: str, chips: int) -> int:
    m = re.search(r"replica_groups=\{\{([0-9, ]+)\}", attrs)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", attrs)
    if m:
        return int(m.group(2))
    return chips


def _dot_flops(op: _Op, symtab: Dict[str, str]) -> float:
    out_bytes, out_dims = _shape_bytes_and_dims(op.type)
    if not out_dims:
        return 0.0
    n_out = 1
    for d in out_dims[0]:
        n_out *= d
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.attrs)
    contract = 1
    if m and op.operands:
        lhs_type = symtab.get(op.operands[0], "")
        _, lhs_dims = _shape_bytes_and_dims(lhs_type)
        if lhs_dims:
            for ix in m.group(1).split(","):
                if ix and int(ix) < len(lhs_dims[0]):
                    contract *= lhs_dims[0][int(ix)]
    return 2.0 * n_out * contract


_SLICERS = {"dynamic-slice", "slice", "gather"}


def _fusion_operand_bytes(comps, shapes, called: str, operands, symtab):
    """Bytes read by a fusion: per operand, if every internal consumer of the
    corresponding parameter is a slice-type op, count the slice results
    instead of the whole buffer (models fused dynamic-slice of stacked/scan
    buffers)."""
    ops = comps.get(called)
    if ops is None:
        return sum(_shape_bytes_and_dims(symtab.get(o, ""))[0]
                   for o in operands)
    param_names = {}
    for op in ops:
        if op.opcode == "parameter":
            m = re.search(r"parameter\((\d+)\)", op.raw)
            if m:
                param_names[int(m.group(1))] = op.name
    total = 0.0
    csyms = shapes[called]
    for i, oname in enumerate(operands):
        full = _shape_bytes_and_dims(symtab.get(oname, ""))[0]
        pname = param_names.get(i)
        if pname is None:
            total += full
            continue
        consumers = [op for op in ops if pname in op.operands]
        if consumers and all(c.opcode in _SLICERS for c in consumers):
            total += sum(_shape_bytes_and_dims(c.type)[0] for c in consumers)
        else:
            total += full
    return total


def _walk(comps, shapes, comp_name, mult, acc, seen_depth=0):
    if comp_name not in comps or seen_depth > 24:
        return
    symtab = shapes[comp_name]
    for op in comps[comp_name]:
        oc = op.opcode
        if oc == "while":
            cond = re.search(r"condition=%?([\w.\-]+)", op.attrs)
            body = re.search(r"body=%?([\w.\-]+)", op.attrs)
            trips = _trip_count(comps, shapes, cond.group(1)) if cond else 1
            acc["while_trips"].append((comp_name, trips))
            if body:
                _walk(comps, shapes, body.group(1), mult * trips, acc,
                      seen_depth + 1)
            continue
        if oc in ("call", "conditional", "async-start"):
            for cm in re.finditer(r"(?:calls|to_apply|body)=%?([\w.\-]+)",
                                  op.attrs):
                _walk(comps, shapes, cm.group(1), mult, acc, seen_depth + 1)
            continue
        if oc == "fusion":
            # dot FLOPs inside the fused computation still execute
            cm = re.search(r"calls=%?([\w.\-]+)", op.attrs)
            if cm and cm.group(1) in comps:
                for op2 in comps[cm.group(1)]:
                    if op2.opcode == "dot":
                        acc["flops"] += mult * _dot_flops(
                            op2, shapes[cm.group(1)])
        if oc == "dot":
            f = mult * _dot_flops(op, symtab)
            acc["flops"] += f
            b_out, _ = _shape_bytes_and_dims(op.type)
            b_in = sum(_shape_bytes_and_dims(symtab.get(o, ""))[0]
                       for o in op.operands)
            acc["bytes_opt"] += mult * (b_out + b_in)
        if oc in _COLL_FACTORS:
            b, _ = _shape_bytes_and_dims(op.type)
            n = _group_size(op.attrs, acc["chips"])
            acc["coll_bytes"] += mult * _COLL_FACTORS[oc](b, n)
            acc["coll_by_kind"][oc.replace("-start", "")] += \
                mult * _COLL_FACTORS[oc](b, n)
            acc["coll_count"][oc.replace("-start", "")] += mult
            acc["bytes_opt"] += mult * b
        if oc not in _SKIP_BYTES:
            b_out, _ = _shape_bytes_and_dims(op.type)
            if oc in ("dynamic-slice", "slice", "gather"):
                # reads only the sliced region, not the whole operand
                acc["bytes"] += mult * 2 * b_out
                acc["bytes_opt"] += mult * 2 * b_out
            elif oc in ("dynamic-update-slice", "scatter"):
                upd = (_shape_bytes_and_dims(symtab.get(op.operands[1], ""))[0]
                       if len(op.operands) > 1 else b_out)
                acc["bytes"] += mult * 2 * upd
                acc["bytes_opt"] += mult * 2 * upd
            elif oc == "copy":
                acc["bytes"] += mult * 2 * b_out
            elif oc == "fusion":
                cm = re.search(r"calls=%?([\w.\-]+)", op.attrs)
                b_in = _fusion_operand_bytes(
                    comps, shapes, cm.group(1) if cm else "", op.operands,
                    symtab)
                acc["bytes"] += mult * (b_out + b_in)
            else:
                b_in = sum(_shape_bytes_and_dims(symtab.get(o, ""))[0]
                           for o in op.operands)
                acc["bytes"] += mult * (b_out + b_in)


def analyze_compiled(hlo_text: str, chips: int) -> dict:
    comps, entry, shapes = parse_hlo(hlo_text)
    acc = {"flops": 0.0, "bytes": 0.0, "bytes_opt": 0.0, "coll_bytes": 0.0,
           "coll_by_kind": defaultdict(float), "coll_count": defaultdict(int),
           "while_trips": [], "chips": chips}
    if entry:
        _walk(comps, shapes, entry, 1.0, acc)
    return {
        "hlo_flops_per_chip": acc["flops"],
        "hlo_bytes_per_chip": acc["bytes"],
        # fusion-optimistic bound: matmul/collective/slice traffic only —
        # what a TPU (or the Pallas kernels) would actually touch in HBM;
        # the pessimistic count charges every CPU-HLO fusion boundary.
        "hlo_bytes_opt_per_chip": acc["bytes_opt"],
        "coll_bytes_per_chip": acc["coll_bytes"],
        "coll_by_kind": {k: round(v) for k, v in acc["coll_by_kind"].items()},
        "coll_count": dict(acc["coll_count"]),
        "while_trips": acc["while_trips"][:16],
    }


# ---------------------------------------------------------------------------
# Roofline terms + analytic model FLOPs
# ---------------------------------------------------------------------------
def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS per the assignment: 6·N·D (train) with N = active params;
    2·N·D forward-only (prefill), 2·N·B (decode, one token/seq)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n * shape.seq_len * shape.global_batch
    return 2.0 * n * shape.global_batch


def roofline_terms(rec: dict) -> dict:
    """Three terms in seconds + dominant bottleneck from a dry-run record.

    The memory term is a [optimistic, pessimistic] pair: the pessimistic
    count charges every CPU-HLO fusion boundary (XLA:CPU materialises far
    more than XLA:TPU); the optimistic one counts matmul + collective +
    slice traffic only (≈ what the Pallas-fused TPU path touches).  The
    headline `rl_frac` (roofline fraction = achievable MFU at the bound)
    uses the optimistic memory term; `rl_frac_pess` keeps the pessimistic.
    """
    chips = rec.get("chips", 256)
    fl = rec.get("hlo_flops_per_chip", 0.0)
    by = rec.get("hlo_bytes_per_chip", 0.0)
    by_o = rec.get("hlo_bytes_opt_per_chip", by)
    co = rec.get("coll_bytes_per_chip", 0.0)
    t_c = fl / PEAK_FLOPS
    t_m = by / HBM_BW
    t_mo = by_o / HBM_BW
    t_i = co / ICI_BW
    dom = max((t_c, "compute"), (t_mo, "memory"), (t_i, "collective"))[1]
    mf = rec.get("model_flops", 0.0)
    total_hlo = fl * chips
    ideal = mf / chips / PEAK_FLOPS
    return {
        "t_compute_s": t_c, "t_memory_s": t_m, "t_memory_opt_s": t_mo,
        "t_collective_s": t_i,
        "dominant": dom,
        "useful_ratio": (mf / total_hlo) if total_hlo else 0.0,
        "roofline_s": max(t_c, t_mo, t_i),
        "mfu_bound": ideal / max(t_c, t_mo, t_i, 1e-30),
        "mfu_bound_pess": ideal / max(t_c, t_m, t_i, 1e-30),
    }


# ---------------------------------------------------------------------------
# Serving-path profiling: roofline ONE PagedJaxBackend decode step
# ---------------------------------------------------------------------------
def roofline_decode_step(arch: str = "tinyllama-1.1b", batch: int = 4,
                         num_blocks: int = 32, page: int = 16,
                         max_len: int = 64, repeats: int = 3,
                         registry=None, steps: int = 1) -> dict:
    """Profile one paged decode dispatch end-to-end (DESIGN.md §9, §10).

    Lowers+compiles the backend's jitted ``decode_paged`` at the padded
    batch bucket, walks the optimized HLO through ``analyze_compiled``,
    pairs it with the analytic 2·N·B decode FLOPs and a best-of-``repeats``
    measured wall time, and reports the roofline terms.  All numbers land
    in ``registry`` as ``roofline_decode_*`` gauges when one is passed.

    With ``steps`` > 1 the record additionally profiles the §10 multi-step
    scan dispatch (``decode_batch_n``'s compiled fn: fused append+attend
    kernel + on-device sampling, ``steps`` micro-steps per dispatch) and
    carries the before/after pair: ``multi_measured_s`` (whole window),
    ``multi_measured_s_per_token``, and ``multi_speedup_per_token`` vs the
    single-step dispatch that returns logits to the host.

    Pallas-opacity: on a TPU the attention kernel compiles to an opaque
    custom-call the HLO walker cannot cost; the record then carries
    ``hlo_opaque=True`` and the HLO-derived terms are lower bounds
    (interpret mode, elsewhere, traces the kernel into plain HLO and
    stays fully costable).  ``mfu_measured`` is reported only for a
    device whose peak is in ``PEAK_FLOPS_BY_KIND``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.shapes import Shape
    from repro.obs import NULL
    from repro.serving.jax_backend import PagedJaxBackend, _bucket

    obs = registry if registry is not None else NULL
    be = PagedJaxBackend(arch, num_blocks=max(num_blocks, batch), page=page,
                         max_len=max_len, seed=0)
    B = _bucket(batch, lo=1)
    # one resident page of context per row (position page-1), distinct
    # pages so the dispatch gathers/scatters like a live mixed batch
    toks = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.full((B,), page - 1, jnp.int32)
    tabs_np = np.full((B, be.n_max), be.scrap, np.int32)
    tabs_np[:, 0] = np.arange(B)
    tabs = jnp.asarray(tabs_np)

    compiled = be._decode.lower(be.params, be.pages, toks, pos,
                                tabs).compile()
    rec = analyze_compiled(compiled.as_text(), chips=1)
    rec["hlo_opaque"] = rec["hlo_flops_per_chip"] <= 0.0
    rec["chips"] = 1
    rec["model_flops"] = model_flops(
        be.cfg, Shape("decode_step", seq_len=page, global_batch=B,
                      kind="decode"))

    import time as _time
    jax.block_until_ready(be._decode(be.params, be.pages, toks, pos, tabs))
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = _time.perf_counter()
        jax.block_until_ready(
            be._decode(be.params, be.pages, toks, pos, tabs))
        best = min(best, _time.perf_counter() - t0)
    rec["measured_s"] = best
    rec.update(roofline_terms(rec))
    peak = PEAK_FLOPS_BY_KIND.get(jax.devices()[0].device_kind)
    if peak is not None:
        rec["mfu_measured"] = rec["model_flops"] / (best * peak)
    rec.update(arch=arch, batch=B, page=page)

    if steps > 1:
        # §10 multi-step dispatch: the scan fn decode_batch_n compiles —
        # rem keeps every lane live for the full window, rids key the
        # on-device sampler
        rem = jnp.full((B,), steps, jnp.int32)
        rids = jnp.arange(1, B + 1, dtype=jnp.int32)
        fn = be._decode_n_fn(steps)
        compiled_n = fn.lower(be.params, be.pages, toks, pos, tabs, rem,
                              rids).compile()
        rec_n = analyze_compiled(compiled_n.as_text(), chips=1)
        jax.block_until_ready(fn(be.params, be.pages, toks, pos, tabs,
                                 rem, rids))
        best_n = float("inf")
        for _ in range(max(repeats, 1)):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(be.params, be.pages, toks, pos, tabs,
                                     rem, rids))
            best_n = min(best_n, _time.perf_counter() - t0)
        rec["multi_steps"] = steps
        rec["multi_hlo_flops_per_chip"] = rec_n["hlo_flops_per_chip"]
        rec["multi_hlo_bytes_per_chip"] = rec_n["hlo_bytes_per_chip"]
        rec["multi_measured_s"] = best_n
        rec["multi_measured_s_per_token"] = best_n / steps
        rec["multi_speedup_per_token"] = best * steps / best_n

    for key in ("hlo_flops_per_chip", "hlo_bytes_per_chip",
                "coll_bytes_per_chip", "model_flops", "t_compute_s",
                "t_memory_s", "t_collective_s", "roofline_s", "measured_s",
                "mfu_bound", "mfu_measured", "multi_measured_s",
                "multi_measured_s_per_token", "multi_speedup_per_token"):
        if key not in rec:
            continue
        obs.gauge(f"roofline_decode_{key}",
                  "paged decode-step roofline profile",
                  arch=arch, batch=str(B)).set(float(rec[key]))
    return rec


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(
        description="Roofline one PagedJaxBackend decode step")
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--num-blocks", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--steps", type=int, default=1,
                    help="also profile the §10 multi-step scan dispatch "
                    "at this horizon (before/after pair in the record)")
    ap.add_argument("--metrics-out", default=None,
                    help="directory for registry snapshots (DESIGN.md §9)")
    args = ap.parse_args(argv)

    registry = None
    if args.metrics_out:
        from repro.obs import MetricsRegistry
        registry = MetricsRegistry()
    rec = roofline_decode_step(
        arch=args.arch, batch=args.batch, num_blocks=args.num_blocks,
        page=args.page, max_len=args.max_len, repeats=args.repeats,
        registry=registry, steps=args.steps)
    print(f"== decode-step roofline: {args.arch} B={rec['batch']} "
          f"page={rec['page']}"
          + (" [HLO opaque: custom-call kernels]" if rec["hlo_opaque"]
             else ""))
    keys = ["hlo_flops_per_chip", "hlo_bytes_per_chip", "model_flops",
            "t_compute_s", "t_memory_s", "roofline_s", "measured_s",
            "mfu_bound", "mfu_measured", "dominant"]
    if args.steps > 1:
        keys += ["multi_steps", "multi_measured_s",
                 "multi_measured_s_per_token", "multi_speedup_per_token"]
    for k in (k for k in keys if k in rec):
        v = rec[k]
        print(f"   {k:<26} {v:.4g}" if isinstance(v, float)
              else f"   {k:<26} {v}")
    if args.metrics_out:
        from repro.obs import dump_all
        paths = dump_all(args.metrics_out, registry=registry,
                         extra={k: rec[k] for k in rec
                                if not isinstance(rec[k], (list, dict))})
        print("   wrote: " + ", ".join(sorted(paths)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
