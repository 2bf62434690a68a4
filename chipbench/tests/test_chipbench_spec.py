"""BENCHMARK.json against the benchmark's contract, and the harness driven
by data: a cell, a traffic mix and a per-layer metric are added as new
files plus new entries, with no code edited, and the added cell runs
through the whole harness at a tiny size on the CPU (the rehearsal path,
which prints no metric)."""

import copy
import importlib.util
import io
import json
import os
import re
import shutil
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}
# keys a width: never in ``reduced``
WIDTH = re.compile(r"(_size$|_dim$|_rank$|intermediate|latent|state|"
                   r"projection|expan|factor|experts_per_tok)")


def _bench(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_paths():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(b["paths"]) <= 16
    for p in b["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= len(b["command"]) <= 32
    for w in b["command"]:
        assert _line(w) and not w.startswith("/") and ".." not in w
        if w.endswith(".py"):
            assert any(w.startswith(p + "/") for p in b["paths"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert len(json.dumps(b)) <= 64 * 1024


def test_configs():
    b = _bench()
    used = {w["config"] for w in b["workloads"]}
    files = set()
    assert 1 <= len(b["configs"]) <= 24
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert len(c["reduced"]) <= 16
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTH.search(k)
            assert k in cfg and cfg["published"][k] != cfg[k]
        # what the program is given agrees with the file's published keys
        s = cfg["serve_as"]
        assert (s["num_layers"], s["d_model"], s["num_heads"],
                s["num_kv_heads"], s["head_dim"], s["d_ff"],
                s["vocab_size"], s["rope_theta"], s["dtype"]) == (
            cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"],
            cfg["rope_theta"], cfg["torch_dtype"])


def test_workloads_and_metrics():
    b = _bench()
    cells = [w["name"] for w in b["workloads"]]
    assert 1 <= len(cells) <= 24 and len(set(cells)) == len(cells)
    pairs = {(w["config"], w["traffic"]) for w in b["workloads"]}
    assert len(pairs) == len(cells)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(cells) // 2)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        for d, ext in (("traffic", w["traffic"]), ("cells", w["name"])):
            assert os.path.isfile(os.path.join(ROOT, "chipbench", d,
                                               ext + ".json"))
    names = set()
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(e2e) <= 16
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                           "source"}
        assert m["source"] in SOURCES_E2E
        assert 0.01 <= m["bound"] <= 0.25
    assert 1 <= len(b["per_layer"]) <= 128
    layers = {}
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert _line(m["layer"])
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "metrics",
                                           m["name"] + ".py"))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"], 0)
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        for c in m.get("workloads", []):
            assert c in cells
    for c in cells:      # every cell reports setup_s, another e2e metric
        got = [m for m in b["end_to_end"] if c in m.get("workloads", cells)]
        assert "setup_s" in {m["name"] for m in got} and len(got) >= 2
        assert any(c in m.get("workloads", cells) for m in b["per_layer"])


# ---------------------------------------------------------------------------
# added by data alone
# ---------------------------------------------------------------------------
TINY = dict(hidden_size=256, intermediate_size=512, num_attention_heads=4,
            num_key_value_heads=2, head_dim=64, num_hidden_layers=2,
            vocab_size=1024)


def tiny_config(cfg):
    cfg = dict(cfg, **TINY)
    cfg["serve_as"] = dict(cfg["serve_as"], d_model=256, d_ff=512,
                           num_heads=4, num_kv_heads=2, head_dim=64,
                           num_layers=2, vocab_size=1024)
    return cfg


TINY_MIX = dict(prompt=dict(mean=12, p50=9, min=4, cap=16),
                output=dict(mean=12, p50=10, min=8, cap=24), rate=6.0,
                preroll_s=1.0)


def rehearsal(cfg):
    return dict(config=tiny_config(cfg), pool_pages=128, mix=TINY_MIX)


def _load_run(root):
    spec = importlib.util.spec_from_file_location(
        "chipbench_run_copy", os.path.join(root, "chipbench", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cell_mix_and_metric_added_as_data(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    b = _bench()
    b2 = copy.deepcopy(b)
    here = os.path.join(root, "chipbench")
    with open(os.path.join(here, "configs", "yi-34b-8l.json")) as f:
        cfg = json.load(f)
    new_cfg = dict(cfg, name="tiny-added")
    json.dump(new_cfg, open(os.path.join(here, "configs",
                                         "tiny-added.json"), "w"))
    with open(os.path.join(here, "traffic", "chat.json")) as f:
        mix = json.load(f)
    json.dump(dict(mix, name="chat_added", rate=3.0),
              open(os.path.join(here, "traffic", "chat_added.json"), "w"))
    json.dump({"max_logit_gap": 1.0},
              open(os.path.join(here, "cells", "tiny.chat_added.json"), "w"))
    with open(os.path.join(here, "metrics", "sched.steps_total.py"),
              "w") as f:
        f.write("def read(ctx):\n    return len(ctx['steps'])\n")
    b2["configs"].append(dict(b["configs"][0], name="tiny-added",
                              file="chipbench/configs/tiny-added.json"))
    b2["workloads"].append(dict(name="tiny.chat_added", config="tiny-added",
                                traffic="chat_added", chips=1, why="test"))
    b2["per_layer"].append(dict(name="sched.steps_total", unit="steps",
                                better="higher", source="program_counter",
                                layer="scheduler", moves="output_tok_s",
                                workloads=["tiny.chat_added"]))
    json.dump(b2, open(os.path.join(root, "BENCHMARK.json"), "w"))

    run = _load_run(root)
    got = run.per_layer(b2, "tiny.chat_added", dict(steps=[1, 2, 3]))
    assert got == {"sched.steps_total": {"value": 3, "unit": "steps"}}
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", "tiny.chat_added", "--seed",
                       str(2**31 + 7), "--seconds", "3", "--trace", "0"],
                      rehearse=rehearsal(new_cfg))
    assert rc == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True and res["metrics"] == {}
    assert res["attempted"] > 5 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert res["compared"]["tokens_missing"]["value"] == 0


def test_refuses_cpu_and_unknown_device_kind(monkeypatch):
    import jax
    from chipbench import run
    with pytest.raises(SystemExit, match="no accelerator"):
        run.device_info(1, rehearse=False)

    class Chip:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    with pytest.raises(SystemExit, match="no peaks"):
        run.device_info(1, rehearse=False)
    with pytest.raises(SystemExit, match="needs 4 chips"):
        run.device_info(4, rehearse=False)


def test_no_result_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files:
    the run exits non-zero and prints nothing on standard output."""
    import subprocess
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(tmp_path, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "yi34b-8l.longdoc", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_pool_sized_from_the_programs_a_probe_dispatches():
    """The pool is sized from the programs that probe backends' protocol
    dispatches, recorded as they are called (no step's signature is
    restated): on the CPU at a tiny size, the widest decode and each probed
    prefill dispatch are recorded, what each adds grows with the pool, and
    the pool chosen fits beside them under the device's limit."""
    import jax
    from chipbench import run
    from chipbench import serve as sv
    _, _, cfg, _, _ = run.find_cell("yi34b-8l.longdoc")
    cfg = tiny_config(cfg)
    w = run.reference(cfg).make_weights(cfg, jax.random.PRNGKey(0))
    groups = sv.prefill_groups(16, 2048, 64)

    class Device:
        device_kind = "test"

        @staticmethod
        def memory_stats():
            return {"bytes_limit": 3 << 30, "bytes_in_use": 1 << 30}

    pool = sv.pool_pages(cfg, w, 64, groups, 64, Device, 3)
    assert pool["programs"] == len(sv.probe_groups(groups)) + 1
    assert all(s > 0 for s in pool["extra_per_page"])
    pages = pool["pages"]
    assert pages % sv.POOL_ROUND == 0 and pages >= sv.PROBE_PAGES[0]
    room = (3 << 30) - sv.POOL_MARGIN_BYTES - (1 << 30)
    p0 = sv.PROBE_PAGES[0]
    slack = [room - (pages + 1) * pool["page_bytes"] - a - (pages - p0) * s
             for a, s in zip(pool["extra_at_probe"], pool["extra_per_page"])]
    assert min(slack) >= 0
    assert min(slack) < (sv.POOL_ROUND + 1) * (
        pool["page_bytes"] + max(pool["extra_per_page"]))
