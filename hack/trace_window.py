"""One ``--trace 1`` window of a benchmark cell, with the trace's device time
by module and scope kept: ``benchmark/run.py`` removes its trace directory
when it ends, and no per-layer metric splits the admit or extend modules by
part (PERF.md section 7, "prefill by part").

    python hack/trace_window.py <tree> <tag> <run.py arguments ...>

runs ``<tree>/benchmark/run.py`` with the arguments (give ``--trace 1``), and
before the trace is removed writes what ``python -m benchmark.trace_spans``
prints for it (per module: seconds by scope and the costliest operations; the
idle by host span) to ``chiprun_out/<tag>.trace.json``; the device's costliest
operations with their whole scope path (``tf_op``: whichever reader lists the
scope; the environment's ``OPS_N`` of them where set, 60 otherwise) to
``chiprun_out/<tag>.ops.json``; the run's result line
goes to ``chiprun_out/<tag>.result.json``; and ``TIMELINE_S`` seconds from the
middle of the trace, laid out on one clock, go to
``chiprun_out/<tag>.timeline.json`` (the environment's ``TIMELINE_S`` where
set, 1.5 otherwise): every run of a device module and every
span of the scheduler's thread as ``[start ms, end ms, name]``, so that one
cycle of the loop can be read beside what the device did meanwhile. The exit
code is the run's. The builder's tool for a chip call, not part of the
benchmark."""
import contextlib
import io
import json
import os
import runpy
import shutil
import sys

tree, tag, argv = os.path.abspath(sys.argv[1]), sys.argv[2], sys.argv[3:]
out_dir = os.path.join(os.getcwd(), "chiprun_out")
os.makedirs(out_dir, exist_ok=True)
os.chdir(tree)
sys.path.insert(0, tree)
rmtree = shutil.rmtree
TIMELINE_S = float(os.environ.get("TIMELINE_S", "1.5"))
OPS_N = int(os.environ.get("OPS_N", "60"))


def timeline(trace_spans, path):
    """The middle TIMELINE_S of the trace: device module runs and the
    scheduler thread's spans (nested ones too), in ms from the cut."""
    found = trace_spans.find_trace(path)
    if found is None:       # a --trace 0 run
        return None
    planes = trace_spans.read_planes(found)
    red = trace_spans.reduce_planes(planes)
    if red is None or red["scheduler_line"] is None:
        return None
    dev = next(p for p in planes
               if p["name"].startswith(trace_spans.DEVICE_PREFIX)
               and any(ln["name"] == trace_spans.OPS_LINE and ln["events"]
                       for ln in p["lines"]))
    mods = sorted(
        (s, e, trace_spans.module_name(dev["meta"].get(mid, ("?", ""))[0]))
        for ln in dev["lines"] if ln["name"] == trace_spans.MODULES_LINE
        for s, e, mid in ln["events"])
    spans = sorted(trace_spans.host_spans(planes)[red["scheduler_line"]],
                   key=lambda ev: (ev[0], -ev[1]))
    mid = (mods[0][0] + mods[-1][1]) // 2
    a, b = mid - int(TIMELINE_S * 5e11), mid + int(TIMELINE_S * 5e11)

    def cut(rows):
        return [[round((s - a) * 1e-9, 3), round((e - a) * 1e-9, 3), n]
                for s, e, n in rows if e > a and s < b]
    return {"seconds": TIMELINE_S, "modules": cut(mods), "spans": cut(spans)}


def costliest_ops(trace_spans, path, n=OPS_N):
    """The device's ``n`` costliest operations by self time over the whole
    trace, each with its count and its full ``tf_op`` (every scope the
    program gave it, whichever reader lists it): ``[seconds, count, name,
    tf_op]``."""
    found = trace_spans.find_trace(path)
    if found is None:
        return []
    total = {}
    for plane in trace_spans.read_planes(found):
        if not plane["name"].startswith(trace_spans.DEVICE_PREFIX):
            continue
        for ln in plane["lines"]:
            if ln["name"] != trace_spans.OPS_LINE:
                continue
            for _s, _e, mid, self_ps in trace_spans.self_times(ln["events"]):
                name, tf_op = plane["meta"].get(mid, ("?", ""))
                row = total.setdefault((name.split(" = ")[0], tf_op), [0, 0])
                row[0] += self_ps
                row[1] += 1
    rows = sorted(((ps * 1e-12, cnt, name, tf_op)
                   for (name, tf_op), (ps, cnt) in total.items()),
                  reverse=True)
    return [list(r) for r in rows[:n]]


def keep_then_remove(path, *a, **kw):
    if os.path.basename(path).startswith("bench-trace-"):
        from benchmark import trace_spans
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            trace_spans.main(["trace_spans", path])
        with open(os.path.join(out_dir, tag + ".trace.json"), "w") as f:
            f.write(buf.getvalue())
        with open(os.path.join(out_dir, tag + ".ops.json"), "w") as f:
            json.dump(costliest_ops(trace_spans, path), f, indent=0)
        tl = timeline(trace_spans, path)
        if tl is not None:
            with open(os.path.join(out_dir, tag + ".timeline.json"), "w") as f:
                json.dump(tl, f)
    return rmtree(path, *a, **kw)


shutil.rmtree = keep_then_remove
sys.argv = [os.path.join(tree, "benchmark", "run.py")] + argv
buf = io.StringIO()


class Tee(io.TextIOBase):
    def write(self, s):
        buf.write(s)
        return sys.__stdout__.write(s)

    def flush(self):
        sys.__stdout__.flush()


code = 0
with contextlib.redirect_stdout(Tee()):
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else int(bool(e.code))
lines = [ln for ln in buf.getvalue().splitlines() if ln.startswith("{")]
if lines:
    with open(os.path.join(out_dir, tag + ".result.json"), "w") as f:
        json.dump(json.loads(lines[-1]), f, indent=1)
sys.exit(code)
