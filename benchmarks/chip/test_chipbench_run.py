"""Whole runs of the harness at a tiny size on the CPU: the look for a
chip is skipped, the rest of a run is driven as on the chip.  A sound
program comes out correct; the float8 control and each fault of the
timed path that a one-chip serving cell can have come out not correct.
(No cell spans chips, so there is no exchange between chips to leave
out.)"""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from chipbench import spec
from chipbench.spec import BENCH_DIR, Cell, read_json

# tiny fixtures' limits, read from CPU runs of these sizes.  Dense, the
# widest gap: sound runs 0 to 0.03, the float8 control 0.35 to 0.74.
# MoE, the mean gap (the widest swings with routing near-ties): sound
# runs 8e-6 to 0.0085, the control 0.026 to 0.045.
LIMITS = {"tiny-dense": {"served_logit_gap": 0.1},
          "tiny-moe": {"served_logit_gap_mean": 0.015}}
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 77


def _run_module():
    s = importlib.util.spec_from_file_location("chipbench_run",
                                               BENCH_DIR / "run.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def _cell(config, traffic, like):
    real = spec.resolve(like)
    return Cell(name=like, chips=1,
                config=read_json(BENCH_DIR / "fixtures" / f"{config}.json"),
                traffic=read_json(BENCH_DIR / "fixtures" / f"{traffic}.json"),
                limits=LIMITS[config],
                end_to_end=real.end_to_end, per_layer=real.per_layer)


def _decode_fault(fault):
    def hook(model):
        step = model.paged_decode_step

        def faulty(p, pages, t, btab, lens, mesh=None):
            logits, new = step(p, pages, t, btab, lens, mesh)
            if fault == "state_unchanged":
                return logits, pages
            if fault == "half_batch":
                h = logits.shape[0] // 2
                return logits.at[h:].set(logits[:logits.shape[0] - h]), new
            if fault == "token_altered":
                return jnp.roll(logits, 1, axis=-1), new
            raise ValueError(fault)
        return dataclasses.replace(model, paged_decode_step=faulty)
    return hook


@pytest.fixture(scope="module")
def run_mod():
    return _run_module()


def test_sound_dense_run_is_correct_and_control_is_not(run_mod):
    cell = _cell("tiny-dense", "tiny_backlog", "nemo12-batch-decode")
    out = run_mod.run_cell(cell, SEED, 1.0, control=True, peak=PEAK)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"setup_s", "out_tok_per_s"}
    assert out["metrics"]["out_tok_per_s"]["value"] > 0
    assert out["device"]["platform"] == "cpu" and out["failed"] == 0
    limit = LIMITS["tiny-dense"]["served_logit_gap"]
    assert out["checks"]["served_logit_gap"]["value"] < limit
    # the float8 control, in the program's place, fails the same limit
    assert out["control_correct"] is False
    ctl = out["control_checks"]["served_logit_gap"]
    assert ctl["limit"] == limit and ctl["value"] > 3 * limit


def test_sound_moe_online_run_is_correct_and_control_is_not(run_mod):
    cell = _cell("tiny-moe", "tiny_online", "granite-moe-online-prefill")
    out = run_mod.run_cell(cell, SEED, 1.5, control=True, peak=PEAK)
    assert out["correct"], out["checks"]
    assert out["control_correct"] is False
    ctl = out["control_checks"]["served_logit_gap_mean"]
    assert ctl["value"] > ctl["limit"]
    assert set(out["metrics"]) == {"setup_s", "itl_p95_ms"}
    assert out["attempted"] >= 5 and out["failed"] == 0


def test_sound_dense_online_run_reports_its_tails(run_mod):
    cell = _cell("tiny-dense", "tiny_online", "nemo12-online-chat")
    out = run_mod.run_cell(cell, SEED + 1, 1.5, peak=PEAK)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "ttft_p90_ms", "itl_p95_ms"}
    assert out["metrics"]["ttft_p90_ms"]["value"] > 0
    assert out["attempted"] >= 5 and out["failed"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_faults_of_the_timed_path_are_not_correct(run_mod, fault):
    cell = _cell("tiny-dense", "tiny_backlog", "nemo12-batch-decode")
    out = run_mod.run_cell(cell, SEED, 1.0, peak=PEAK,
                           model_hook=_decode_fault(fault))
    assert not out["correct"], out["checks"]
    gap = out["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


def _cli(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "nemo12-batch-decode", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (json.JSONDecodeError, TypeError):
            continue
    return False


def test_command_refuses_the_cpu():
    proc = _cli(spec.ROOT, {})
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "never runs on the CPU" in proc.stderr


def test_command_fails_in_a_tree_of_only_the_benchmark(tmp_path):
    (tmp_path / "benchmarks").mkdir()
    subprocess.run(["cp", "-r", str(BENCH_DIR), str(tmp_path / "benchmarks")],
                   check=True)
    (tmp_path / "BENCHMARK.json").write_text(
        (spec.ROOT / "BENCHMARK.json").read_text())
    proc = _cli(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
