"""The latent-attention MoE cell on the CPU: at a tiny size the whole
harness runs it (the rehearsal path, which prints no metric) and its output
check passes, with the program's float32 latent path against the plain
reference; at the published widths with fewer layers and a smaller
vocabulary the float8 control fails the cell's limit; and the new
per-layer readers, on a reduced trace."""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CELL = "dsv2lite-ep8.longgen"
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=4, head_dim=16, num_hidden_layers=3,
            vocab_size=512, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, moe_intermediate_size=64,
            n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
            experts_held_first=2)
TINY_SERVE = dict(num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
                  head_dim=16, d_ff=128, vocab_size=512, kv_lora_rank=32,
                  qk_nope_dim=16, qk_rope_dim=16, v_head_dim=16,
                  d_ff_expert=64, num_experts=8, experts_held=4,
                  experts_first=2, num_shared_experts=1, top_k=2)
TINY_MIX = dict(prompt=dict(mean=12, p50=9, min=4, cap=16),
                output=dict(mean=12, p50=10, min=8, cap=24), rate=6.0,
                preroll_s=1.0)


def tiny(cfg):
    cfg = dict(cfg, **TINY)
    cfg["published"] = dict(cfg["published"], n_routed_experts=8)
    cfg["serve_as"] = dict(cfg["serve_as"], **TINY_SERVE)
    return cfg


@pytest.fixture(scope="module")
def run_mod():
    from chipbench import run
    return run


def test_rehearsal_is_correct(run_mod):
    _, _, cfg, _, _ = run_mod.find_cell(CELL)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run_mod.main(["--workload", CELL, "--seed", str(2**31 + 11),
                           "--seconds", "3", "--trace", "0"],
                          rehearse=dict(config=tiny(cfg), pool_pages=128,
                                        mix=TINY_MIX))
    assert rc == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True, res
    assert res["compared"]["max_logit_gap"]["value"] < 1e-3


def test_control_reads_far_above_the_program(run_mod):
    """On the same served requests, the reference in float8 puts tokens
    first that lie far further below the float32 best than any the
    program served: the relation the cell's limit is set in on the chip.
    At this size (width 64, 3 layers) logits spread about 0.16 and the
    control reads about 0.02, under the cell's limit; the next test reads
    it at the scale where it fails that limit."""
    from chipbench import serve as sv
    from chipbench import traffic as tr
    _, _, cfg, _, _ = run_mod.find_cell(CELL)
    s = run_mod.build(CELL, 2**31 + 5, 3.0,
                      rehearse=dict(config=tiny(cfg), pool_pages=128,
                                    mix=TINY_MIX))
    mix = s["mix"]
    arrs = tr.arrivals(mix, mix["preroll_s"] + 3.0, 7)
    toks = tr.prompt_tokens(arrs, s["k"]["V"], 7)
    res = sv.serve(s["be"], arrs, toks, s["warm"], mix["preroll_s"], 3.0)
    res.pop("engine")
    readings, ctrl = run_mod.check(res, toks, s["cfg"], s["weights"],
                                   s["ref"], mix["compare_requests"], 7,
                                   control=True)
    assert readings["tokens_checked"] > 20 and readings["tokens_missing"] == 0
    assert ctrl > 10 * readings["max_logit_gap"], (readings, ctrl)


def test_control_fails_the_cell_limit_at_published_widths(run_mod):
    """The float8 control on seeded weights and tokens at every published
    width, with 4 of the 27 layers and a vocabulary of 32768 (what the CPU
    holds in seconds), over 256 positions: the widest gap of the token it
    puts first lies above the cell's limit, so ``is_correct`` refuses it.
    On the chip, at the cell's own size, it reads higher still
    (``cells/dsv2lite-ep8.longgen.json``)."""
    import jax
    from chipbench.references import mla_moe
    _, _, cfg, _, limits = run_mod.find_cell(CELL)
    cfg = dict(cfg, num_hidden_layers=4, vocab_size=32768)
    w = mla_moe.make_weights(cfg, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(0, 32768, 512)
    _, ctrl = mla_moe.gaps(cfg, w, toks[:256], toks[256:], fp8_control=True)
    readings = dict(max_logit_gap=float(ctrl.max()), tokens_missing=0,
                    requests_checked=1)
    assert not run_mod.is_correct(run_mod.correct_of(readings, limits)), \
        (readings, limits)


def test_readers_on_a_reduced_trace():
    import importlib.util
    from chipbench import cost_mla_moe
    from chipbench.serve import Step
    from chipbench.references import mla_moe

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "deepseek-v2-lite-ep8.json")) as f:
        k = mla_moe.dims(json.load(f))
    assert k["E"] == 8 and k["E_all"] == 64
    assert cost_mla_moe.routed_share(k) == 0.75
    steps = [Step(0.1 * i, 0.1 * i + 0.05, 0, 20, 20 * 3000, 0.5, [])
             for i in range(10)]
    red = dict(op_s={"jit_decode_scan/mla_decode_attention.3 [pallas]": 0.01,
                     "jit_decode_scan/mla_decode_attention.7 [pallas]": 0.01,
                     "jit_decode_scan/moe_gmm.2 [pallas]": 0.05,
                     "jit_prefill_paged/moe_gmm.1 [pallas]": 9.0},
               program_n={"jit_decode_scan": 10}, window_s=1.0)
    ctx = dict(k=k, peak={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
               steps=steps, traced=(0.0, 1.0), trace=red)

    def read(name):
        path = os.path.join(ROOT, "chipbench", "metrics", name + ".py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(ctx)

    f, by = cost_mla_moe.decode_mla(k, 20, 60000)
    assert read("kernel.mla_decode_roofline") == pytest.approx(
        100 * 10 * by / 819e9 / 0.02)
    assert read("step.moe_ms") == pytest.approx(5.0)
    touched = 8 * (1 - (1 - 6 / 64) ** 20)
    assert 0 < read("kernel.moe_gmm_roofline") < 100
    assert cost_mla_moe.decode_moe(k, 20)[1] > touched * 26 * 3 * 2048 \
        * 1408 * 2
    assert 0 < read("step.mfu_mla_moe") < 100
    # a program without the kernels: no number, no error
    red["op_s"] = {"jit_decode_scan/fusion.1": 0.1}
    assert read("kernel.mla_decode_roofline") is None
    assert read("step.moe_ms") is None
