"""bench.py orchestration specs: a TPU or an error, never a stand-in.

The benchmark's number is a chip number.  These specs pin what happens
when there is no chip (this sandbox: ``JAX_PLATFORMS=cpu``, no TPU):

  * the default budget arithmetic fits the total deadline,
  * with no chip, bench.py prints an error result — no value under the
    chip metric's name — and exits nonzero, quickly,
  * a driver SIGTERM mid-run still yields a parseable final JSON line,
    marked truncated, and a nonzero exit code.
"""

import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _bench_env(**over):
    env = dict(os.environ)
    for k in ("BENCH_FAKE_PROBE_HANG", "BENCH_TIMEOUT",
              "BENCH_PROBE_TIMEOUT", "BENCH_TPU_TIMEOUT"):
        env.pop(k, None)
    env.update({k: str(v) for k, v in over.items()})
    return env


def _last_json_line(stdout: str):
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    assert lines, f"no output: {stdout!r}"
    return json.loads(lines[-1])


def test_no_chip_prints_error_result_and_exits_nonzero():
    """No TPU: one failed probe, an error result with a null value, a
    nonzero exit code — and no CPU measurement in between (the whole
    run is one backend start-up long)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_under_test", BENCH)
    b = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(b)
    assert (b.DEFAULT_PROBE_TIMEOUT + b.DEFAULT_TPU_TIMEOUT + 90.0
            <= b.DEFAULT_TIMEOUT <= 1800)

    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, BENCH], stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, cwd=REPO, timeout=110,
        env=_bench_env(JAX_PLATFORMS="cpu"),
    )
    assert time.time() - t0 < 100
    assert proc.returncode != 0
    res = _last_json_line(proc.stdout)
    assert res["metric"] == "resnet50_train_images_per_sec_per_chip"
    assert res["value"] is None and res["mfu"] is None
    assert res["platform"] is None
    assert "no TPU" in res["error"]
    assert "@@BENCH_PARTIAL@@" not in proc.stdout


def test_sigterm_mid_probe_prints_json_and_exits_nonzero():
    """The driver's `timeout` sends SIGTERM: bench.py must trap it and
    print a parseable JSON line as its final output — and must not
    report success for a run that was cut short."""
    env = _bench_env(
        BENCH_FAKE_PROBE_HANG=300,
        BENCH_PROBE_TIMEOUT=250,
        BENCH_TIMEOUT=400,
        JAX_PLATFORMS="cpu",
    )
    # on a loaded machine the interpreter may take longer than the first
    # wait to reach the line that installs the handler: a child the
    # signal killed before that (no output, -SIGTERM) is tried again
    # with a longer wait, and says nothing about bench.py
    for wait in (1.5, 6.0, 20.0):
        proc = subprocess.Popen(
            [sys.executable, BENCH], stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, env=env, cwd=REPO,
        )
        time.sleep(wait)  # parent is now blocked inside the probe wait
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=30)
        if out.strip() or proc.returncode != -signal.SIGTERM:
            break
    assert proc.returncode not in (0, None)
    res = _last_json_line(out)
    assert res["metric"] == "resnet50_train_images_per_sec_per_chip"
    assert res["value"] is None
    assert "signal" in (res["error"] or "")
