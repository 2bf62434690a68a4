"""Cross-layer integration: real JAX decoding under Tempo, the serve
failover drill, and one true dry-run cell compiled against the 256-chip
production mesh in a subprocess (the multi-pod config is exercised by the
full sweep in experiments/dryrun)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_real_jax_serving_with_tempo():
    """The unified run loop (ServeEngine) drives real JAX decoding on the
    paged device KV cache under Tempo — RealServeLoop's old dead-end fork
    is retired (DESIGN.md §2)."""
    from repro.core.scheduler import TempoScheduler
    from repro.serving.engine import EngineConfig, ServeEngine
    from repro.serving.jax_backend import PagedJaxBackend
    from repro.serving.request import Request, SLOSpec
    reqs = [Request(rid=i + 1, app="chatbot", arrival=0.0, prompt_len=12,
                    true_output_len=8 + 2 * i,
                    slo=SLOSpec("latency", ttft=1e6, tbt=1e6))
            for i in range(3)]
    be = PagedJaxBackend("tinyllama-1.1b", num_blocks=12, page=16,
                         max_len=32, seed=0)
    eng = ServeEngine(be, TempoScheduler(use_predictor=False),
                      EngineConfig(max_batch=4, prefill_budget=32))
    eng.load(reqs, [])
    eng.run()
    assert all(r.done for r in reqs)
    assert all(len(be.generated[r.rid]) == r.true_output_len for r in reqs)


def test_serve_failover_drill():
    from repro.core.service import ServiceModel
    from repro.launch.serve import run_with_failover
    from repro.serving.workload import WorkloadSpec
    s, info = run_with_failover(
        "sarathi", WorkloadSpec(rate=3.0, duration=40.0, seed=2),
        fail_at=20.0, service=ServiceModel())
    assert info["resubmitted"] > 0
    assert s.n_finished > 50           # everything drains post-recovery


@pytest.mark.slow
def test_dryrun_single_cell_production_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "tinyllama-1.1b", "--shape", "decode_32k"],
        capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout)
    assert rec["status"] == "ok" and rec["chips"] == 256
    assert rec["hlo_flops_per_chip"] > 0
    assert rec["coll_bytes_per_chip"] > 0


def test_compile_cache_follows_env_else_fixed_repo_path(monkeypatch):
    import jax

    from repro.serving.run import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere/cache"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                            ".."))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_dryrun_pins_itself_to_cpu_and_keeps_xla_flags(monkeypatch):
    from repro.launch import dryrun
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/dev/null")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    dryrun._pin_to_cpu()
    dryrun._pin_to_cpu()
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert os.environ["XLA_FLAGS"] == (
        "--xla_dump_to=/dev/null --xla_force_host_platform_device_count=512")
