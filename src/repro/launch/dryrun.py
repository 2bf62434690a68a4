"""Multi-pod dry-run: lower + compile every (architecture × input shape) cell
against the production mesh with ShapeDtypeStruct inputs (no allocation).

Usage:
  python -m repro.launch.dryrun --arch yi-34b --shape train_4k [--multi-pod]
      [--decode-tp] [--attn triangle] [--out out.json]
  python -m repro.launch.dryrun --all [--multi-pod]   # driver: subprocesses

Per cell this prints/records compiled.memory_analysis() (fits-per-device
evidence) and compiled.cost_analysis() (FLOPs/bytes for §Roofline), plus the
optimized HLO's collective inventory parsed by repro.launch.roofline.
"""

import argparse
import json
import os
import subprocess
import sys
import time

_HOST_DEVICES = "--xla_force_host_platform_device_count=512"


def _pin_to_cpu() -> None:
    """The dry-run is a CPU tool: before JAX starts, hold this process —
    and, through the environment they inherit, its children — to the
    CPU backend with 512 virtual devices, so no dry-run ever claims an
    accelerator.  Appends to ``XLA_FLAGS`` instead of replacing it."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if _HOST_DEVICES not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_HOST_DEVICES}".strip()


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             decode_tp: bool = False, attn_schedule: str = "rect",
             save_hlo: str = "", extra: dict | None = None) -> dict:
    import jax
    from repro.configs.base import get_config
    from repro.configs.shapes import applicable, get_shape
    from repro.launch import sharding as sh
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import analyze_compiled
    from repro.launch.steps import (make_prefill_step, make_serve_step,
                                    make_train_step)

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    rec: dict = dict(arch=arch, shape=shape_name,
                     multi_pod=multi_pod, decode_tp=decode_tp,
                     attn_schedule=attn_schedule)
    if extra:
        rec.update(extra)
    if not applicable(cfg, shape):
        rec["status"] = "skip(full-attn)"
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()

    ctx = sh.make_ctx(cfg, mesh, shape.kind, decode_tp=decode_tp,
                      attn_schedule=attn_schedule)

    with mesh:
        if shape.kind == "train":
            model, opt, _ = make_train_step(cfg, ctx)
            specs = model.input_specs(shape)
            params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            opt_s = jax.eval_shape(opt.init, params_s)
            p_sh = sh.params_shardings(cfg, mesh, params_s)
            model, opt, step = make_train_step(cfg, ctx, grad_shardings=p_sh)
            o_sh = sh.opt_shardings(cfg, mesh, opt_s)
            b_sh = sh.batch_shardings(ctx, specs["batch"])
            jf = jax.jit(step,
                         in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1))
            lowered = jf.lower(params_s, opt_s, specs["batch"])
        elif shape.kind == "prefill":
            model, step = make_prefill_step(cfg, ctx)
            specs = model.input_specs(shape)
            params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            p_sh = sh.params_shardings(
                cfg, mesh, params_s, mode="tp" if decode_tp else "fsdp")
            b_sh = sh.batch_shardings(ctx, specs["batch"])
            cache_s = model.cache_specs(shape.global_batch, shape.seq_len)
            c_sh = sh.cache_shardings(ctx, cache_s)
            jf = jax.jit(step, in_shardings=(p_sh, b_sh),
                         out_shardings=(None, c_sh))
            lowered = jf.lower(params_s, specs["batch"])
        else:  # decode
            model, step = make_serve_step(cfg, ctx)
            specs = model.input_specs(shape)
            params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            p_sh = sh.params_shardings(
                cfg, mesh, params_s, mode="tp" if decode_tp else "fsdp")
            c_sh = sh.cache_shardings(ctx, specs["caches"])
            t_sh = sh.batch_shardings(ctx, {"tokens": specs["tokens"]})["tokens"]
            jf = jax.jit(step,
                         in_shardings=(p_sh, c_sh, t_sh, None),
                         out_shardings=(None, c_sh),
                         donate_argnums=(1,))
            lowered = jf.lower(params_s, specs["caches"], specs["tokens"],
                               specs["index"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    rec.update(status="ok", chips=chips,
               lower_s=round(t_lower, 1), compile_s=round(t_compile, 1))

    mem = compiled.memory_analysis()
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                rec[attr] = int(v)
        args = rec.get("argument_size_in_bytes", 0)
        alias = rec.get("alias_size_in_bytes", 0)
        out = rec.get("output_size_in_bytes", 0)
        tmp = rec.get("temp_size_in_bytes", 0)
        rec["per_device_bytes"] = args + tmp + max(0, out - alias)

    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):        # older JAX: one dict per device
        ca = ca[0] if ca else None
    if ca:
        rec["xla_flops_oncethrough"] = float(ca.get("flops", 0.0))
        rec["xla_bytes_oncethrough"] = float(ca.get("bytes accessed", 0.0))

    # Trip-count-aware walk of the optimized HLO (collectives + dot FLOPs).
    hlo = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    rec.update(analyze_compiled(hlo, chips=chips))

    # analytic model FLOPs for the §Roofline "useful compute" ratio
    from repro.launch.roofline import model_flops
    rec["model_flops"] = model_flops(cfg, shape)
    return rec


def main() -> None:
    _pin_to_cpu()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--decode-tp", action="store_true")
    ap.add_argument("--attn", default="rect", choices=["rect", "triangle"])
    ap.add_argument("--out", default="")
    ap.add_argument("--save-hlo", default="")
    ap.add_argument("--all", action="store_true",
                    help="driver: run every cell in a subprocess")
    ap.add_argument("--outdir", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all:
        from repro.configs.shapes import all_cells
        os.makedirs(args.outdir, exist_ok=True)
        failures = []
        for arch, shape_name, runnable in all_cells():
            tag = f"{arch}__{shape_name}" + ("__mp" if args.multi_pod else "")
            out = os.path.join(args.outdir, tag + ".json")
            if os.path.exists(out):
                print(f"[skip existing] {tag}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name, "--out", out]
            if args.multi_pod:
                cmd.append("--multi-pod")
            print(f"[run] {tag}", flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True)
            if r.returncode != 0:
                failures.append(tag)
                with open(out + ".err", "w") as f:
                    f.write(r.stdout + "\n" + r.stderr)
                print(f"[FAIL] {tag}: {r.stderr.strip().splitlines()[-1:]}" ,
                      flush=True)
        print(f"done; failures: {failures}")
        sys.exit(1 if failures else 0)

    rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   decode_tp=args.decode_tp, attn_schedule=args.attn,
                   save_hlo=args.save_hlo)
    js = json.dumps(rec, indent=2, default=str)
    print(js)
    if args.out:
        with open(args.out, "w") as f:
            f.write(js)


if __name__ == "__main__":
    main()
