"""What one host span costs: ``span()`` entered and left N times, with no
profiler session (an observation of ``tpu_model_span_seconds`` and a
thread-local push and pop) and inside one (a ``TraceAnnotation`` besides, at
the host tracer level the benchmark's child uses), nested in a parent span as
the engine's are. Prints nanoseconds a span, the median of five rounds each.
Host-side only: run it on the machine whose host serves (``chiprun -- python
hack/span_cost.py``); no device number comes out of it.

    python hack/span_cost.py [N]
"""
import json
import os
import statistics
import sys
import tempfile
import time

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from ollama_operator_tpu.runtime.trace import span  # noqa: E402

N = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000


def round_ns(name: str, **fields) -> float:
    with span("sched.admit"):
        t0 = time.perf_counter_ns()
        for _ in range(N):
            with span(name, **fields):
                pass
        return (time.perf_counter_ns() - t0) / N


def rounds(name: str, **fields) -> float:
    return statistics.median(round_ns(name, **fields) for _ in range(5))


t0 = time.perf_counter_ns()
for _ in range(N):
    pass
loop = (time.perf_counter_ns() - t0) / N
out = {"n": N, "platform": jax.devices()[0].platform, "empty_loop_ns": loop,
       "no_session_ns": {"engine.enqueue": rounds("engine.enqueue",
                                                  program="admit"),
                         "sched.stall": rounds("sched.stall",
                                               cause="pool_dry_admit")}}
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 1
with tempfile.TemporaryDirectory() as d:
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        out["in_session_ns"] = {"engine.enqueue": rounds(
            "engine.enqueue", program="admit")}
    finally:
        jax.profiler.stop_trace()
print(json.dumps(out))
