"""Device time of one decode step under the ``attn.kv_write`` scope (the
step's new K and V rows and their scales written into the page pool or the
contiguous cache): self time of the decode module's operations in the trace,
over the steps of its complete runs (benchmark/trace_spans.py). None where
the trace has no decode module or none of its operations carries the scope:
XLA's scatter fusions lose their ``op_name``, so a program that writes the
pool by scatter reads low or nothing here (PERF.md, PR 30)."""
from benchmark import trace_spans

UNIT = "ms"


def read(ctx):
    parts = trace_spans.decode_step_parts(
        trace_spans.reduce(), ctx.resolved.get("decode_chunk"))
    if parts is None or "attn.kv_write" not in parts["by_scope_s"]:
        return None
    return 1e3 * parts["by_scope_s"]["attn.kv_write"]
