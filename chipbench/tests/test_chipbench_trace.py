"""Trace reduction and the FLOP/byte functions.

The interval arithmetic is checked on hand-built events; the reading of a
real trace on ``chipbench/testdata/tiny.xplane.pb``, recorded on a TPU v5e
by a traced run of the harness at a tiny width (2 layers, d_model 256), whose
device planes, program names and kernel names are those of the full-size
cells."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from chipbench import cost  # noqa: E402
from chipbench import trace_reduce as trr  # noqa: E402

TRACE = os.path.join(ROOT, "chipbench", "testdata", "tiny.xplane.pb")
E = trr.Event


def test_union_and_idle_gaps():
    evs = [E("a", 0, 10), E("b", 5, 20), E("c", 30, 40), E("d", 38, 45)]
    assert trr.union(evs) == [(0, 20), (30, 45)]
    assert trr.busy_ns(evs) == 35
    assert trr.idle_gaps(evs, -5, 50) == [(-5, 0), (20, 30), (45, 50)]
    assert trr.busy_ns(trr.clip(evs, 8, 35)) == 12 + 5


def test_by_name_leaves_and_short_names():
    ops = [E("dot", 0, 4), E("dot", 10, 13), E("add", 4, 5), E("dot", 30, 31)]
    assert trr.by_name(ops) == {"dot": 8, "add": 1}
    loop = [E("while", 0, 10), E("a", 0, 4), E("b", 5, 10), E("c", 12, 13)]
    assert [e.name for e in trr.leaves(loop)] == ["a", "b", "c"]
    assert trr.short("%fusion.3 = bf16[2]{0} fusion(...)") == "fusion.3"
    assert trr.short('%closed_call.4 = (bf16[1]) custom-call(...), '
                     'custom_call_target="tpu_custom_call"') == \
        "closed_call.4 [pallas]"


def test_gap_labelled_by_innermost_covering_span():
    host = [(E("engine.step_once", 0, 100), 0), (E("sched.schedule", 10, 40),
                                                  1),
            (E("backend.step_time", 60, 100), 1)]
    assert trr.label_gap(host, 12, 38) == "sched.schedule"
    assert trr.label_gap(host, 45, 55) == "engine.step_once"
    assert trr.label_gap(host, 200, 210) == "host (no span)"


def test_reduce_on_events():
    tr = trr.Trace(devices={"/device:TPU:0": [E("fusion", 0, 30),
                                             E("kernel", 50, 90)]},
                   modules={"/device:TPU:0": [E("jit_a", 0, 30),
                                             E("jit_b", 50, 90)]},
                   host=[(E("harness.wait", 30, 50), 0)])
    red = trr.reduce(tr, 0, 100)
    assert red["busy_s"] == pytest.approx(70e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["program_s"] == {"jit_a": 30e-9, "jit_b": 40e-9}
    assert red["program_n"] == {"jit_a": 1, "jit_b": 1}
    assert red["idle_gaps"][0] == ["harness.wait", 20e-9]
    assert red["device_ops"][0] == ["jit_b/kernel", 40e-9]
    assert trr.seconds_matching(red, "program_s", "_b$") == 40e-9
    assert trr.seconds_matching(red, "program_s", "nothing") is None


def test_recorded_chip_trace():
    tr = trr.load(TRACE, ("engine.", "sched.", "backend.", "harness."))
    assert any(p.startswith("/device:TPU") for p in tr.devices)
    hs = [e for e, _ in tr.host]
    assert {e.name for e in hs} >= {"engine.step_once", "sched.schedule"}
    red = trr.reduce(tr, min(e.start for e in hs), max(e.end for e in hs))
    assert 0 < red["busy_s"] < red["window_s"]
    # leaf operations leave out only the loop bookkeeping between them
    assert 0.9 * red["busy_s"] <= sum(red["op_s"].values()) <= red["busy_s"]
    assert trr.seconds_matching(red, "program_s", "^jit__unknown$") > 0
    assert trr.seconds_matching(red, "program_s", "prefill") > 0
    kernel = r"^jit__unknown/.*\[pallas\]$"
    assert trr.seconds_matching(red, "op_s", kernel) > 0
    assert all(lab.split(".")[0] in ("engine", "sched", "backend", "harness",
                                     "host (no span)")
               for lab, _ in red["idle_gaps"])


# -- operations and bytes --------------------------------------------------
K = dict(d=64, H=4, KV=2, hd=16, ff=128, L=2, V=100)


def test_matmul_params_and_prefill_flops():
    # wq 64*64 + wk,wv 2*64*32 + wo 64*64 + mlp 3*64*128
    assert cost.matmul_params(K) == 4096 + 4096 + 4096 + 24576
    # 3 tokens after 2 resident: positions 2,3,4 see 3+4+5 = 12 keys
    f = cost.prefill_chunk_flops(K, 2, 3)
    assert f == 2 * cost.matmul_params(K) * 2 * 3 + 4 * 4 * 16 * 2 * 12


def test_step_and_decode_attention():
    # two sequences with 10 and 20 tokens before this one
    f = cost.step_flops(K, [], 2, 30)
    assert f == 2 * (2 * cost.matmul_params(K) * 2 + 2 * 64 * 100) \
        + 4 * 4 * 16 * 32 * 2
    fl, by = cost.decode_attn(K, 2, 30)
    assert fl == 4 * 4 * 16 * 32 * 2
    kv = 2 * 2 * 16 * 2
    assert by == 2 * (kv * 32 + kv * 2 + 2 * 4 * 16 * 2 * 2)
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    t, bound = cost.least_time(fl, by, peak)
    assert bound == "memory" and t == pytest.approx(by / 1e9)


# -- readers that must fail rather than measure something else -------------
def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(ROOT, "chipbench", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Step:
    def __init__(self, t0, seqs, chunks=()):
        self.t0, self.t1 = t0, t0 + 1
        self.decode_seqs, self.decode_ctx = seqs, 100 * seqs
        self.chunks = list(chunks)
        self.prefill_tokens = sum(n for _, n in chunks)


def _decode_ctx(programs):
    """A reduction of a window of 10 decode steps whose compiled programs
    (name with its number -> (runs, Pallas calls)) are ``programs``."""
    n, s, ks, ops = {}, {}, {}, {}
    for mod, (runs, kernels) in programs.items():
        name = trr.program_name(mod)
        n[name] = n.get(name, 0) + runs
        s[name] = s.get(name, 0.0) + 0.01 * runs
        ks[mod] = kernels
        for k in kernels:
            ops[f"{name}/{k}"] = ops.get(f"{name}/{k}", 0.0) + 0.005 * runs
    red = dict(program_n=n, program_s=s, program_kernels=ks, op_s=ops)
    steps = [_Step(i, 4) for i in range(10)]
    return dict(trace=red, steps=steps, traced=(0, 20), k=K,
                peak={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9})


def test_recorded_trace_has_one_kernel_per_decode_program():
    tr = trr.load(TRACE, ("engine.", "sched.", "backend.", "harness."))
    hs = [e for e, _ in tr.host]
    red = trr.reduce(tr, min(e.start for e in hs), max(e.end for e in hs))
    progs = trr.programs_matching(red, r"^jit__unknown$")
    assert len(progs) >= 2           # one compiled program per decode width
    assert all(len(ks) == 1 and ks[0].endswith("[pallas]")
               for ks in progs.values())


def test_decode_readers_read_a_sound_window():
    ctx = _decode_ctx({"jit__unknown(1)": (6, ["closed_call.3 [pallas]"]),
                       "jit__unknown(2)": (4, ["closed_call.9 [pallas]"]),
                       "jit_prefill_paged(3)": (2, [])})
    assert _reader("step.decode_ms").read(ctx) == pytest.approx(10.0)
    assert _reader("kernel.decode_attn_roofline").read(ctx) > 0


@pytest.mark.parametrize("programs", [
    # a second program of the decode program's name runs in every step
    {"jit__unknown(1)": (10, ["closed_call.3 [pallas]"]),
     "jit__unknown(2)": (10, [])},
    # the decode program was renamed
    {"jit_scan_step(1)": (10, ["closed_call.3 [pallas]"])},
])
def test_decode_readers_refuse_other_programs(programs):
    ctx = _decode_ctx(programs)
    for name in ("step.decode_ms", "kernel.decode_attn_roofline"):
        with pytest.raises(ValueError, match="ran"):
            _reader(name).read(ctx)


def test_roofline_refuses_a_decode_program_with_two_kernels():
    ctx = _decode_ctx({"jit__unknown(1)": (10, ["closed_call.3 [pallas]",
                                                "closed_call.4 [pallas]"])})
    with pytest.raises(ValueError, match="Pallas"):
        _reader("kernel.decode_attn_roofline").read(ctx)


def test_prefill_reader_counts_one_program_per_bucket():
    # chunks of 5 and 7 tokens share bucket 8; 100 tokens go to bucket 128
    steps = [_Step(0, 0, [(0, 5), (0, 7), (0, 100)]), _Step(2, 0, [(0, 9)])]
    red = dict(program_n={"jit_prefill_paged": 2,
                          "jit__prefill_many_impl": 1},
               program_s={"jit_prefill_paged": 0.002,
                          "jit__prefill_many_impl": 0.001})
    ctx = dict(trace=red, steps=steps, traced=(0, 10))
    got = _reader("step.prefill_us_per_tok").read(ctx)
    assert got == pytest.approx(1e6 * 0.003 / 121)
    red["program_n"]["jit_prefill_paged"] = 9
    with pytest.raises(ValueError, match="ran 10 times"):
        _reader("step.prefill_us_per_tok").read(ctx)
