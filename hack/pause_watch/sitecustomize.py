"""Which process pauses when a benchmark run loses seconds (PERF.md section
7 (r))? With this directory on PYTHONPATH every Python process of a run (the
harness, the server child, the load generator) says on stderr when a garbage
collection takes over 50 ms (``GCPAUSE``, with the generation) and when a
50 ms heartbeat thread wakes over 300 ms late (``LATEBEAT``: something held
the GIL, or the host stalled: then two processes say so at one moment).

    chiprun -- env PYTHONPATH=/root/repo/hack/pause_watch python3 \
        benchmark/run.py --workload <cell> --seed <n> --seconds 51 --trace 0

The builder's tool (PR 48); nothing imports it and no cell runs it."""
import gc
import os
import sys
import threading
import time

_t = {}


def _cb(phase, info):
    if phase == "start":
        _t["t"] = time.perf_counter()
        return
    dt = time.perf_counter() - _t.get("t", time.perf_counter())
    if dt > 0.05:
        sys.stderr.write(
            f"GCPAUSE pid={os.getpid()} argv={' '.join(sys.argv[:3])[:60]} "
            f"gen={info['generation']} {dt * 1e3:.0f} ms "
            f"collected={info['collected']} at={time.time():.3f}\n")
        sys.stderr.flush()


def _beat():
    last = time.perf_counter()
    while True:
        time.sleep(0.05)
        now = time.perf_counter()
        if now - last > 0.35:
            sys.stderr.write(
                f"LATEBEAT pid={os.getpid()} {1e3 * (now - last):.0f} ms "
                f"at={time.time():.3f}\n")
            sys.stderr.flush()
        last = now


gc.callbacks.append(_cb)
threading.Thread(target=_beat, daemon=True, name="diag-beat").start()
