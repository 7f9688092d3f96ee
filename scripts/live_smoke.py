#!/usr/bin/env python
"""--live smoke: the live telemetry plane, end to end.

Driven by ``scripts/run-tests.sh --live``.  Five stages, each a hard
assert:

1. two simulated hosts (separate OS processes, ``BIGDL_PROCESS_ID``
   0/1) run a 40-step DistriOptimizer job with live servers on
   **ephemeral** ports (``BIGDL_OBS_PORT=0`` + port files), the input
   pipeline synthetically starved for the first ~24 steps and healthy
   after — so the ``goodput_slo_burn`` alert must fire, then resolve;
2. while both are RUNNING, the driver scrapes each host's ``/metrics``
   (must parse completely, with ``# HELP``/``# TYPE`` on every family)
   and ``/healthz`` (an advancing step stamp), and a peer-mode
   ``FleetAggregator`` snapshot must merge both hosts;
3. after the run, the alert lifecycle is checked: ``alert.firing`` AND
   ``alert.resolved`` trace events for ``goodput_slo_burn``, with
   matching ``bigdl_alerts_total``/``bigdl_alerts_resolved_total``;
4. ``report --watch --once`` renders the alerts section in text and
   carries it (plus the fleet snapshot) in ``--json``;
5. the supervisor hang watchdog: a deliberately stalled child (stamps
   one step, then wedges) is killed and restarted, the restarted
   attempt completes — and a control run with ``BIGDL_OBS_PORT`` unset
   holds no server thread, no socket, and no step stamp (the seed
   off-path; the compiled-signature pin itself lives in
   tests/test_obs_health.py's disabled-signature spec).

Exit 0 only when all five hold.
"""

import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
# a CPU smoke: keep jax off any accelerator (the chip belongs to one
# process at a time, and this script starts several)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_WORKER = """
import os, sys, time, threading
sys.path.insert(0, os.environ["BIGDL_REPO"])
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") \\
    + " --xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import bigdl_tpu.native as native
from bigdl_tpu.engine import Engine
from bigdl_tpu.nn import (ClassNLLCriterion, Linear, LogSoftMax, ReLU,
                          Sequential)
from bigdl_tpu.optim import DistriOptimizer, SGD, Trigger

# synthetic SLO violation: the first STARVE_BATCHES batches arrive
# late (window goodput ratio collapses -> burn-rate breach), the rest
# arrive promptly (the breach resolves before the run ends)
_P = native.PrefetchIterator
_DELIVERED = [0]

class HalfStarved:
    def __init__(self, iterable, depth=2):
        self._it = iter(_P(iterable, depth))

    def __iter__(self):
        return self

    def __next__(self):
        if _DELIVERED[0] < int(os.environ.get("SMOKE_STARVE_BATCHES",
                                              "24")):
            time.sleep(float(os.environ.get("SMOKE_BATCH_DELAY",
                                            "0.05")))
        _DELIVERED[0] += 1
        return next(self._it)

if os.environ.get("SMOKE_NO_OBS") != "1":
    native.PrefetchIterator = HalfStarved

Engine.init()
rng = np.random.RandomState(0)
w = rng.randn(16, 4)
x = rng.randn(320, 16).astype(np.float32)
y = (np.argmax(x @ w, axis=1) + 1).astype(np.float32)
model = Sequential().add(Linear(16, 32)).add(ReLU()) \\
    .add(Linear(32, 4)).add(LogSoftMax())
opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(), batch_size=32)
opt.set_optim_method(SGD(learningrate=0.1))
opt.set_end_when(Trigger.max_iteration(40))
opt.optimize()
assert opt.state["neval"] == 41, opt.state["neval"]

from bigdl_tpu.obs import server
if os.environ.get("SMOKE_NO_OBS") == "1":
    # the off-path pin: no server object, no daemon thread, no stamp
    assert opt._obs_server is None, "server built without BIGDL_OBS_PORT"
    assert server.get_server() is None
    assert not [t for t in threading.enumerate()
                if t.name == "bigdl-obs-server"], "stray server thread"
    assert server.last_step() == (None, None), "stamp without a server"
    print("NO_OBS_PIN_OK")
else:
    assert server.get_server() is not None
    assert server.last_step()[0] == 40
"""

_STALLER = """
import os, sys, time
sys.path.insert(0, os.environ["BIGDL_REPO"])
from bigdl_tpu.obs import server
s = server.ensure_server()
assert s is not None, "staller must bind its ephemeral endpoint"
if int(os.environ.get("BIGDL_ELASTIC_ATTEMPT", "0")) >= 1:
    sys.exit(0)                 # the restarted attempt completes
server.note_step(1)
time.sleep(300)                 # wedged: alive, never advances
"""


def _env(**extra):
    e = dict(os.environ)
    e.update({k: str(v) for k, v in extra.items()})
    e["BIGDL_REPO"] = REPO
    e["JAX_PLATFORMS"] = "cpu"
    return e


def _get(url, timeout=3.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode("utf-8")


def _wait_port(port_file, deadline):
    while time.time() < deadline:
        try:
            with open(port_file, encoding="utf-8") as fh:
                port = int(fh.read().strip() or 0)
            if port:
                return port
        except (OSError, ValueError):
            pass
        time.sleep(0.1)
    raise AssertionError(f"no port file at {port_file}")


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="bigdl_live_smoke_")
    trace_dir = os.path.join(tmp, "trace")
    metrics_dir = os.path.join(tmp, "metrics")

    # -- 1: two live hosts on ephemeral ports -------------------------
    workers, port_files = [], []
    for host in (0, 1):
        pf = os.path.join(tmp, f"port.h{host}")
        port_files.append(pf)
        workers.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER],
            env=_env(BIGDL_PROCESS_ID=host, BIGDL_TRACE_DIR=trace_dir,
                     BIGDL_METRICS_DIR=metrics_dir,
                     BIGDL_GOODPUT_WINDOW=4, BIGDL_OBS_PORT=0,
                     BIGDL_OBS_PORT_FILE=pf),
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    deadline = time.time() + 120
    ports = [_wait_port(pf, deadline) for pf in port_files]
    print(f"[live-smoke] two hosts up on ephemeral ports {ports}")

    # -- 2: live scrapes + fleet merge, mid-run -----------------------
    from bigdl_tpu.obs.aggregate import FleetAggregator
    from bigdl_tpu.obs.metrics import parse_prometheus, sample_value

    for host, port in enumerate(ports):
        # wait until the host resolved its first step (live, not idle)
        while time.time() < deadline:
            h = json.loads(_get(f"http://127.0.0.1:{port}/healthz"))
            if h.get("step"):
                break
            assert workers[host].poll() is None, "worker died early"
            time.sleep(0.2)
        assert h["host"] == host and h["status"] == "ok", h
        assert h["step"] >= 1 and h["step_age_s"] is not None, h
        text = _get(f"http://127.0.0.1:{port}/metrics")
        parsed = parse_prometheus(text)  # loud on any malformed line
        assert "# TYPE bigdl_engine_inits_total counter" in text
        assert "# HELP bigdl_engine_inits_total" in text
        assert sample_value(parsed, "bigdl_engine_inits_total") == 1
        tail = json.loads(_get(f"http://127.0.0.1:{port}/trace?last=16"))
        assert tail, "flight-recorder tail empty with tracing on"
        print(f"[live-smoke] host {host}: live /metrics "
              f"({len(parsed['samples'])} samples, HELP/TYPE ok), "
              f"/healthz step {h['step']}, /trace tail {len(tail)}")

    peers = ",".join(f"127.0.0.1:{p}" for p in ports)
    fleet = FleetAggregator(peers=peers).snapshot()
    assert fleet["mode"] == "peers" and not fleet["errors"], fleet
    assert set(fleet["hosts"]) == {"0", "1"}, fleet["hosts"].keys()
    for host, row in fleet["hosts"].items():
        # the autoscaler's queue signal rides every host row (None on
        # a non-streaming run like this one — the key must exist)
        assert "queue_depth" in row and row["queue_depth"] is None, row
    print(f"[live-smoke] fleet snapshot merged hosts "
          f"{sorted(fleet['hosts'])} from {peers}")

    for host, w in enumerate(workers):
        out, err = w.communicate(timeout=300)
        assert w.returncode == 0, \
            f"host {host} worker failed:\n{out}\n{err}"

    # -- 3: alert fired AND resolved, with matching counters ----------
    p = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.obs.report", trace_dir,
         "--metrics-dir", metrics_dir, "--json"],
        env=_env(), cwd=REPO, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr
    rep = json.loads(p.stdout.strip().splitlines()[-1])
    al = rep["alerts"]
    states = {e["state"] for e in al["events"]
              if e.get("rule") == "goodput_slo_burn"}
    assert states == {"firing", "resolved"}, al["events"]
    fired = al["fired_total"].get("goodput_slo_burn[warning]", 0)
    resolved = al["resolved_total"].get("goodput_slo_burn", 0)
    assert fired >= 1 and fired == resolved, \
        f"fired {fired} != resolved {resolved}"
    assert "goodput_slo_burn" not in al["active"], al["active"]
    print(f"[live-smoke] goodput_slo_burn fired {int(fired)}x and "
          f"resolved {int(resolved)}x (matching counts)")

    # -- 4: report --watch --once renders alerts, text + --json -------
    p = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.obs.report", trace_dir,
         "--metrics-dir", metrics_dir, "--watch", "--once"],
        env=_env(), cwd=REPO, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr
    for needle in ("-- live fleet (shards) --", "-- alerts --",
                   "goodput_slo_burn[warning]"):
        assert needle in p.stdout, \
            f"watch frame missing {needle!r}:\n{p.stdout}"
    p = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.obs.report", trace_dir,
         "--metrics-dir", metrics_dir, "--watch", "--once", "--json"],
        env=_env(), cwd=REPO, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr
    wrep = json.loads(p.stdout.strip().splitlines()[-1])
    assert wrep["fleet"]["hosts"], wrep["fleet"]
    assert wrep["alerts"]["fired_total"], wrep["alerts"]
    print("[live-smoke] report --watch --once renders the alerts "
          "section (text + --json, with the fleet header)")

    # -- 5a: supervisor hang watchdog kills + restarts a wedged child -
    staller = os.path.join(tmp, "staller.py")
    with open(staller, "w", encoding="utf-8") as fh:
        fh.write(_STALLER)
    p = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.resilience.supervisor",
         "--max-retries", "2", "--hang-timeout", "2", "--",
         sys.executable, staller],
        env=_env(BIGDL_OBS_PORT=0, BIGDL_RETRY_BACKOFF_BASE=0),
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "killing the hung child" in p.stderr, p.stderr
    assert "(hang)" in p.stderr, p.stderr
    print("[live-smoke] hang watchdog killed the wedged child; the "
          "restarted attempt completed (rc 0)")

    # -- 5b: BIGDL_OBS_PORT unset binds nothing -----------------------
    env_off = _env(BIGDL_PROCESS_ID=0, SMOKE_NO_OBS=1)
    for var in ("BIGDL_OBS_PORT", "BIGDL_OBS_PORT_FILE", "BIGDL_OBS",
                "BIGDL_TRACE_DIR", "BIGDL_METRICS_DIR"):
        env_off.pop(var, None)
    p = subprocess.run(
        [sys.executable, "-c", _WORKER],
        env=env_off, cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "NO_OBS_PIN_OK" in p.stdout, p.stdout
    print("[live-smoke] control run without BIGDL_OBS_PORT: no thread, "
          "no socket, no step stamp")
    print("[live-smoke] PASS")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
