"""Shared by the benchmark's tests: a temporary copy of the benchmark
with throw-away cells added as new files, and a rehearsal of one run of
a cell on the CPU in a process of its own."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data")

# (file in tests/data, directory of the copy it is added to)
ADDED = [
    ("tiny_gpt.json", "configs"), ("tiny_resnet.json", "configs"),
    ("tiny_closed4.json", "traffic"), ("tiny_train.json", "traffic"),
    ("tiny_train_data4.json", "traffic"), ("tokens_per_step.py", "metrics"),
]

REHEARSE = """
import sys
sys.path[:0] = [{copy!r}, {repo!r}]
import jax
from benchmarks import run
{before}
sys.exit(run.main({argv!r}, devices=jax.devices()[:{chips}]))
"""


def make_copy(tmp: str) -> str:
    """Copy ``benchmarks/`` and ``BENCHMARK.json`` to ``tmp``, then add a
    configuration, a mix, a cell and a per-layer metric of each kind as
    NEW files and entries; no file that was there is edited."""
    copy = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(copy, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {}
    for root, _, files in os.walk(os.path.join(copy, "benchmarks")):
        for f in files:
            path = os.path.join(root, f)
            with open(path, "rb") as fh:
                before[path] = fh.read()
    for name, where in ADDED:
        dst = os.path.join(copy, "benchmarks", where, name)
        assert not os.path.exists(dst), dst
        shutil.copy(os.path.join(DATA, name), dst)
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, f"{path} was edited"
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    bench["configs"] += [
        {"name": "tiny_gpt", "source": "tests", "reduced": [], "why": "test",
         "file": "benchmarks/configs/tiny_gpt.json"},
        {"name": "tiny_resnet", "source": "tests", "why": "test",
         "reduced": ["image_size", "num_classes"],
         "file": "benchmarks/configs/tiny_resnet.json"}]
    bench["workloads"] += [
        {"name": "tiny_serve", "config": "tiny_gpt", "traffic": "tiny_closed4",
         "chips": 1, "why": "test"},
        {"name": "tiny_train", "config": "tiny_resnet",
         "traffic": "tiny_train", "chips": 1, "why": "test"},
        {"name": "tiny_distri", "config": "tiny_resnet",
         "traffic": "tiny_train_data4", "chips": 4, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" not in m:
            continue
        if "gpt2xl_gen_heavy" in m["workloads"]:
            m["workloads"].append("tiny_serve")
        if "resnet50_train_1chip" in m["workloads"]:
            m["workloads"] += ["tiny_train", "tiny_distri"]
        if m["name"] == "collective_exposed_ms":
            m["workloads"].append("tiny_distri")
    bench["per_layer"].append(
        {"name": "tokens_per_step", "unit": "tokens", "better": "higher",
         "source": "program_counter", "layer": "serving host loop",
         "moves": "serve_tokens_per_s", "workloads": ["tiny_serve"]})
    with open(os.path.join(copy, "BENCHMARK.json"), "w",
              encoding="utf-8") as fh:
        json.dump(bench, fh, indent=1)
    return copy


def rehearse(copy: str, workload: str, seed: int = 5, seconds: float = 2.0,
             trace: int = 0, chips: int = 1, before: str = "",
             timeout: float = 900.0):
    """Run one cell of the copy on the CPU, skipping only the look for a
    chip.  Returns (exit code, result object or None, all output)."""
    argv = ["--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    code = REHEARSE.format(copy=copy, repo=REPO, argv=argv, chips=chips,
                           before=before)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=copy, env=env,
                          capture_output=True, text=True, timeout=timeout)
    result = None
    lines = proc.stdout.strip().splitlines()
    if proc.returncode == 0 and lines:
        result = json.loads(lines[-1])
    return proc.returncode, result, proc.stdout + proc.stderr
