"""What can be pinned on the CPU about the chip bring-up.

* ``chip_smoke.py`` refuses to run off the chip: under
  ``JAX_PLATFORMS=cpu`` it names the platform, exits nonzero before any
  phase and prints no result line.  When it does pass, the last line of
  its stdout is the result with exactly the keys the driver reads.
* The persistent compile cache is placed once, at package import, from
  outside: ``JAX_COMPILATION_CACHE_DIR`` wins and the code sets nothing;
  unset, the cache goes to the one fixed in-checkout directory, the same
  in every process; runs pinned to the CPU keep no cache.  Importing the
  package starts no backend either way.
* The native feed library is rebuilt when its source is newer.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_REPORT = (
    "import json, sys; sys.path.insert(0, {repo!r}); import bigdl_tpu, jax; "
    "from jax._src import xla_bridge; "
    "print(json.dumps({{'dir': jax.config.jax_compilation_cache_dir, "
    "'backends': len(xla_bridge._backends)}}))"
).format(repo=REPO)


def _spawn(argv, cwd, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS")}
    env.update(env_over)
    return subprocess.Popen(argv, cwd=cwd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Every subprocess of this module, started together (each pays a
    jax import; side by side they cost one)."""
    tmp = tmp_path_factory.mktemp("chip_smoke")
    outside = str(tmp / "given_cache")
    report = [sys.executable, "-c", _REPORT]
    procs = {
        "env": _spawn(report, REPO, JAX_COMPILATION_CACHE_DIR=outside,
                      JAX_PLATFORMS="tpu"),
        "unset_a": _spawn(report, REPO, JAX_PLATFORMS="tpu"),
        "unset_b": _spawn(report, str(tmp)),  # other cwd, auto platform
        "cpu": _spawn(report, REPO, JAX_PLATFORMS="cpu"),
        "smoke": _spawn([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                        REPO, JAX_PLATFORMS="cpu"),
        # the script's frame with no phase in it: start-up, then the
        # two closing lines
        "frame": _spawn([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                         "--rehearse-cpu", "--phases", ""], REPO),
    }
    done = {}
    for name, proc in procs.items():
        out, err = proc.communicate(timeout=120)
        done[name] = (proc.returncode, out, err)
    done["outside"] = outside
    return done


def test_compile_cache_is_placed_from_outside_or_at_one_fixed_path(runs):
    got = {}
    for name in ("env", "unset_a", "unset_b", "cpu"):
        rc, out, err = runs[name]
        assert rc == 0, err[-2000:]
        got[name] = json.loads(out.strip().splitlines()[-1])
    # importing the package never starts a backend (no chip is touched:
    # JAX_PLATFORMS=tpu above would fail here if one were)
    assert all(r["backends"] == 0 for r in got.values()), got
    # set from outside: JAX reads the variable, the code adds nothing
    assert got["env"]["dir"] == runs["outside"]
    # unset: one fixed directory inside the checkout, whatever the
    # process, the cwd or the moment
    fixed = os.path.join(REPO, ".jax_cache")
    assert got["unset_a"]["dir"] == got["unset_b"]["dir"] == fixed
    # CPU runs keep no cache: the chip tool copies the checkout as it
    # stands, and a cached CPU executable may meet another host's CPU
    assert got["cpu"]["dir"] is None
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_chip_smoke_refuses_to_run_off_the_chip(runs):
    rc, out, err = runs["smoke"]
    assert rc != 0
    assert "platform: cpu" in out
    assert "--- phase" not in out                  # before any phase
    assert '"ok"' not in out                       # and no result line
    assert "not 'tpu'" in err


def test_chip_smoke_ends_with_the_result_the_driver_reads(runs):
    rc, out, err = runs["frame"]
    assert rc == 0, err[-2000:]
    summary, result = (json.loads(ln) for ln in out.strip().splitlines()[-2:])
    # the last line: these keys and no other
    assert set(result) == {"ok", "device"} and result["ok"] is True
    dev = result["device"]
    assert set(dev) == {"platform", "kind", "count"}
    assert dev["platform"] == "cpu" and isinstance(dev["kind"], str)
    assert type(dev["count"]) is int
    # the line before it: the summary, which claims nothing
    assert summary["device"] == dev and summary["rehearsal"] is True
    assert list(summary)[-1] == "claim" and summary["claim"] is None


def test_native_library_is_stale_when_its_source_is_newer(tmp_path,
                                                          monkeypatch):
    from bigdl_tpu import native

    src, so = tmp_path / "lib.cpp", tmp_path / "lib.so"
    monkeypatch.setattr(native, "_SRC_PATH", str(src))
    monkeypatch.setattr(native, "_SO_PATH", str(so))
    src.write_text("// source")
    assert native._stale()                         # no binary yet
    so.write_bytes(b"")
    os.utime(src, (1000, 1000))
    os.utime(so, (2000, 2000))
    assert not native._stale()                     # binary is newer
    os.utime(src, (3000, 3000))
    assert native._stale()                         # source edited since
