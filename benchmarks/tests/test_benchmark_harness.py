"""The harness end to end on the CPU, in a temporary copy to which a
configuration, a mix, a cell and a per-layer metric were added as new
files, with no edit to any file that was there."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.tests import helpers


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return helpers.make_copy(str(tmp_path_factory.mktemp("bench")))


def test_run_py_on_the_cpu_is_an_error_and_never_a_number():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "resnet50_train_1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=helpers.REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "no result" in proc.stderr


def test_run_py_without_the_program_is_an_error(tmp_path, copy):
    # a directory that holds only BENCHMARK.json and the benchmark
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "tiny_serve",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=copy,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_added_serving_cell_runs_with_files_and_entries_only(copy):
    rc, result, out = helpers.rehearse(copy, "tiny_serve", seed=2**31 + 77,
                                       seconds=2.0)
    assert rc == 0, out
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    for m in result["metrics"].values():
        assert m["value"] > 0
    assert result["device"]["platform"] == "cpu"   # a rehearsal, and says so
    assert "check served_gap_mean" in out
    assert "compiled inside the window" not in out


def test_a_traced_run_with_no_device_operation_is_refused(copy):
    # the CPU has no device plane: a traced run must not invent one
    rc, result, out = helpers.rehearse(copy, "tiny_serve", seconds=1.0,
                                       trace=1)
    assert rc != 0 and result is None
    assert "no device operation" in out


def test_added_metric_reader_is_found_by_its_name(copy):
    sys.path.insert(0, copy)
    try:
        spec_path = os.path.join(copy, "benchmarks", "metrics",
                                 "tokens_per_step.py")
        assert os.path.exists(spec_path)
        with open(os.path.join(copy, "BENCHMARK.json")) as fh:
            bench = json.load(fh)
        assert any(m["name"] == "tokens_per_step" for m in bench["per_layer"])
    finally:
        sys.path.remove(copy)


BREAK_TOKEN = """
from bigdl_tpu.serving import engine as _e
_orig = _e.LMEngine._step
def _broken(self):
    # a token altered where it is produced: every decode step's tokens
    # are replaced before the engine reads them
    fn = self._step_fn
    def wrong(*a):
        kp, vp, nxt = fn(*a)
        return kp, vp, (nxt + 1) % 64
    self._step_fn = wrong
    try:
        return _orig(self)
    finally:
        self._step_fn = fn
_e.LMEngine._step = _broken
"""


def test_a_broken_timed_path_comes_out_not_correct(copy):
    rc, result, out = helpers.rehearse(copy, "tiny_serve", seconds=2.0,
                                       before=BREAK_TOKEN)
    assert rc == 0, out
    assert result["correct"] is False, out
    assert "FAILED" in out
