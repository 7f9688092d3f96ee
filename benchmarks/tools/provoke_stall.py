"""Stop a run of the benchmark for a second inside its window: a check
of the stall watch's labels on the real machine (PR 50).

    python benchmarks/tools/provoke_stall.py [--after 12] [--stop 1.0] \
        -- --workload gpt2xl_gen_heavy --seed 7 --seconds 45 --trace 1

Runs ``benchmarks/run.py`` with the arguments behind ``--`` as a child
(this process never touches JAX, so the child has the chip), passes its
output through, and once the child prints the line that comes just
before its window opens (a serving driver's ``ramp:``) waits ``--after``
seconds, sends it ``SIGSTOP``, and ``--stop`` seconds later ``SIGCONT``.
The traced run must then hold one ``obs.stall`` of about ``--stop``
seconds with ``cause=process_stopped`` and ``watch_late_ms`` about as
long, and report ``host_stall_share.serve`` of about ``--stop`` over the
window.  Hand-run; no driver calls it.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# the last line a serving driver prints before it opens the window
OPENS_AFTER = "ramp:"


def stop_for(pid: int, after_s: float, stop_s: float):
    time.sleep(after_s)
    os.kill(pid, signal.SIGSTOP)
    t0 = time.time()
    try:
        time.sleep(stop_s)
    finally:
        os.kill(pid, signal.SIGCONT)
    print(f"provoke_stall: stopped pid {pid} for {time.time() - t0:.3f}s "
          f"from {time.strftime('%H:%M:%S', time.gmtime(t0))} UTC",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--after", type=float, default=12.0,
                    help="seconds into the window at which to stop the run")
    ap.add_argument("--stop", type=float, default=1.0)
    ap.add_argument("run_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    run_args = [a for a in args.run_args if a != "--"]
    child = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         *run_args], cwd=ROOT, stdout=subprocess.PIPE, text=True)
    stopper = None
    for line in child.stdout:
        sys.stdout.write(line)
        sys.stdout.flush()
        if stopper is None and line.startswith(OPENS_AFTER):
            stopper = threading.Thread(
                target=stop_for, args=(child.pid, args.after, args.stop))
            stopper.start()
    rc = child.wait()
    if stopper is None:
        print(f"provoke_stall: the run printed no {OPENS_AFTER!r} line; "
              "nothing was stopped", file=sys.stderr)
        return rc or 1
    stopper.join()
    return rc


if __name__ == "__main__":
    sys.exit(main())
