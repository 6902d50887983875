"""A whole run on the CPU at toy size, the chip check skipped: a sound
run is correct, the lower-precision control and each planted fault are
not.  And the command itself refuses to measure without a TPU."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from chipbench import harness, spec

FIXTURES = Path(__file__).resolve().parent / "fixtures"
SEED = 2**31 + 12345


def _run(cell="tiny.tinychat", **kw):
    return harness.run_cell(spec.load_cell(cell, FIXTURES), SEED, 1.5, False,
                           t_start=time.perf_counter(), require_chip=False,
                           **kw)


def test_sound_run_is_correct_and_the_control_is_not():
    r = _run(controls=("fp8",))
    assert r["attempted"] > 10 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "output_tokens_per_s",
                                 "ttft_p50_ms", "ttft_p95_ms",
                                 "tpot_p50_ms", "tpot_p95_ms"}
    assert r["correct"] is True
    assert r["control_correct"] == {"fp8": False}
    gap, ctl = r["check"]["max_logit_gap"], \
        r["check"]["control_fp8_max_logit_gap"]
    assert gap["value"] <= gap["limit"] < ctl["value"]


def test_a_closed_loop_stops_at_the_window_and_checks_what_it_finished():
    seen = {}
    r = _run("tiny.tinydocs", on_window=lambda load, recs: seen.update(
        late=load.t_end - load.window[1]))
    assert seen["late"] < 0.5  # no tails to follow after the window
    assert set(r["metrics"]) == {"setup_s", "output_tokens_per_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["correct"] is True


@pytest.mark.parametrize("fault", ["token_altered", "kv_unwritten",
                                   "half_batch"])
def test_a_planted_fault_makes_the_run_incorrect(fault):
    r = _run(fault=fault)
    assert r["correct"] is False
    gap = r["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_command_refuses_without_a_tpu():
    bench = Path(harness.__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload",
         "qwen1.5-0.5b.chat", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=bench.parents[1], timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    with pytest.raises(json.JSONDecodeError):
        json.loads(p.stdout.strip().splitlines()[-1] if p.stdout.strip()
                   else "")
