"""The benchmark's command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell's entry in ``BENCHMARK.json``, finds its configuration
(``benchmarks/configs/<config>.json``), its traffic mix
(``benchmarks/traffic/<traffic>.json``) and the driver the configuration
names in ``kind`` (``benchmarks/drivers/<kind>.py``) by name, runs the
cell once and prints, as the last line of standard output, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
and, in a traced run, ``breakdown``.  With ``--trace 0`` the metrics are
the cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics,
each read by ``benchmarks/metrics/<name>.py``.

There is no fallback: without a TPU, or with fewer chips than the cell
asks for, the command prints no result and exits 2.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def find_cell(bench: dict, name: str):
    for cell in bench["workloads"]:
        if cell["name"] == name:
            cfg = next(c for c in bench["configs"]
                       if c["name"] == cell["config"])
            return cell, cfg
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                     f"{[c['name'] for c in bench['workloads']]}")


def load_cell(bench: dict, name: str):
    """The cell's entry, its configuration file and its mix's file."""
    cell, cfg_entry = find_cell(bench, name)
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    mix = load_json(os.path.join(ROOT, "benchmarks", "traffic",
                                 cell["traffic"] + ".json"))
    return cell, config, mix


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def metric_reader(name: str):
    """The ``read(run)`` of ``benchmarks/metrics/<name>.py``; a name may
    hold dots, so the file is loaded by its path."""
    path = os.path.join(ROOT, "benchmarks", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.metrics." + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class Run:
    """What a per-layer metric's reader is given: the cell, its files,
    and what the run recorded.  ``spans`` are the program's host spans
    inside the window, ``counters`` the driver's counts, ``trace`` the
    reduced device trace (``lib/xplane.reduce``) or None."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def require_chips(chips: int):
    """The chips this cell runs on, or exit 2 without a result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmarks/run.py: need {chips} TPU chip(s), JAX offers "
              f"{len(devices)} device(s) of platform "
              f"{devices[0].platform!r}; no result", file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, devices) -> dict:
    """One run of one cell on ``devices``; returns the result object."""
    from benchmarks.lib import harness, peaks, xplane

    cell, config, mix = load_cell(bench, workload)
    kind = devices[0].device_kind
    out_dir = os.path.join(ROOT, ".bench_out", workload)
    os.makedirs(out_dir, exist_ok=True)
    opened = {}
    ctx = {
        "cell": cell, "config": config, "traffic": mix, "seed": seed,
        "seconds": seconds, "trace": trace, "devices": devices,
        "out_dir": out_dir, "compiles": harness.CompileLog(),
        "mark_open": lambda t: opened.setdefault("t", t),
    }
    driver = importlib.import_module(f"benchmarks.drivers.{config['kind']}")
    out = driver.run(ctx)
    setup_s = opened["t"] - T_PROCESS
    out["check"].print()
    e2e = dict(out["e2e"], setup_s=setup_s)
    print("end_to_end " + json.dumps(e2e), flush=True)
    print(f"compile cache: {ctx['compiles'].cache_hits} hits, "
          f"{ctx['compiles'].cache_misses} misses; "
          f"{len(ctx['compiles'].compiles)} compilations", flush=True)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": out["check"].correct,
              "attempted": out["attempted"], "failed": out["failed"]}
    metrics = {}
    if not trace:
        for m in bench["end_to_end"]:
            if applies(m, workload):
                if m["name"] not in e2e:
                    raise SystemExit(
                        f"the run gave no value for {m['name']}")
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        reduced = out["profile"].reduce()
        if not reduced or reduced["busy_s"] <= 0:
            raise SystemExit("the traced window holds no device operation")
        run = Run(cell=cell, config=config, traffic=mix, chips=len(devices),
                  device_kind=kind, peaks=peaks.peaks_for(kind),
                  seconds=seconds, window_s=out["window_s"], e2e=e2e,
                  spans=out["spans"], counters=out["counters"],
                  trace=reduced, extra=out)
        for m in bench["per_layer"]:
            if not applies(m, workload):
                continue
            value = metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": xplane.top(reduced["ops"]),
            "idle_gaps": xplane.top(reduced["idle_gaps"]),
        }
    result["metrics"] = metrics
    result["device"] = device
    return result


def main(argv=None, devices=None) -> int:
    """``devices`` is for the benchmark's own tests, which rehearse a
    run on the CPU's devices; the command line always looks for chips."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, _ = find_cell(bench, args.workload)
    if args.trace:
        # the program's own span tracer (an existing switch of the
        # program) is on in the traced run only
        os.environ["BIGDL_TRACE_DIR"] = os.path.join(
            ROOT, ".bench_out", args.workload, "obs")
    # importing the package places the compile cache (a fixed path in
    # the checkout, or where JAX_COMPILATION_CACHE_DIR says)
    import bigdl_tpu  # noqa: F401
    import jax

    # keep sub-second compiles too: a cell's small programs (page-table
    # slices, key splits, the weights) would otherwise compile in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if devices is None:
        devices = require_chips(int(cell["chips"]))
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
