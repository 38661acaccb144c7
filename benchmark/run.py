"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX. It reads the cell from ``BENCHMARK.json``,
finds the cell's configuration, traffic mix, numbers and per-layer readers by
name under ``benchmark/``, starts ONE child (``server_child.py``) that holds
the chip and serves the model through the program's own ``serve()``, and is
itself the load generator. Every line on stdout is one JSON object; the last
is the result the driver reads. ``--rehearse`` runs the same control flow on
the CPU at toy widths and can never print a line that reads as a result.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import types

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmark import loadgen, prom, stats, traffic_gen, work  # noqa: E402

READY_TIMEOUT_S = 1150.0     # a cell's first run compiles its warm plan
DRAIN_S = 30.0               # longer than the longest request of any mix here

# serving knobs a zero-config start must not inherit from our environment
SERVING_ENV = ("TPU_ENGINE_DTYPE", "TPU_KV_DTYPE", "TPU_PAGED",
               "TPU_PAGE_SIZE", "TPU_N_PAGES", "TPU_MAX_SLOTS",
               "TPU_DECODE_CHUNK", "TPU_MAX_SEQ_LEN", "TPU_TENSOR_PARALLEL",
               "TPU_SEQUENCE_PARALLEL", "TPU_EXPERT_PARALLEL",
               "TPU_DATA_PARALLEL", "TPU_WARM_BUCKETS", "TPU_XLA_CACHE",
               "TPU_PAGED_V3", "TPU_PAGED_V4", "TPU_PAGED_FUSED",
               "TPU_PREFIX_CACHE", "TPU_SPEC_DECODE", "OLLAMA_TPU_KERNELS",
               "TPU_MIN_PREFILL_BUCKET", "TPU_PREFILL_CHUNK",
               "TPU_HTTP_WORKERS", "TPU_STREAM_FLUSH_TOKENS",
               "TPU_STREAM_FLUSH_MS", "TPU_ASYNC_DISPATCH", "TPU_FUSED_QKV")


class RunFailure(Exception):
    pass


def say(**rec) -> None:
    print(json.dumps(rec), flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str) -> types.SimpleNamespace:
    """The cell's entry, configuration, mix, numbers and metrics, each found
    by its name in BENCHMARK.json."""
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunFailure(f"no workload {name!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    entry = cells[name]
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == entry["config"])
    numbers_path = os.path.join(HERE, "cells", name + ".json")

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return types.SimpleNamespace(
        name=name, chips=int(entry["chips"]),
        conf_path=os.path.join(REPO, conf_entry["file"]),
        conf=work.load_conf(os.path.join(REPO, conf_entry["file"])),
        mix_name=entry["traffic"],
        mix=traffic_gen.load_mix(entry["traffic"]),
        numbers=(load_json(numbers_path)
                 if os.path.exists(numbers_path) else {}),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def layer_reader(name: str):
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Child:
    """The server child and the conversation with it."""

    def __init__(self, proc):
        self.proc = proc
        self.lines: "asyncio.Queue[dict]" = asyncio.Queue()
        self.replies: "asyncio.Queue[dict]" = asyncio.Queue()
        self.seen: dict = {}         # the newest line of each phase
        self.pump = asyncio.ensure_future(self._pump())

    @classmethod
    async def start(cls, cell, seed: int, trace_dir: str, rehearse: bool):
        env = {k: v for k, v in os.environ.items() if k not in SERVING_ENV}
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        args = [sys.executable, os.path.join(HERE, "server_child.py"),
                "--config", cell.conf_path, "--seed", str(seed),
                "--chips", str(cell.chips), "--trace-dir", trace_dir]
        if rehearse:
            env.update(JAX_PLATFORMS="cpu", OLLAMA_TPU_KERNELS="interpret")
            args.append("--rehearse")
        proc = await asyncio.create_subprocess_exec(
            *args, cwd=REPO, env=env, stdin=asyncio.subprocess.PIPE,
            stdout=asyncio.subprocess.PIPE, limit=1 << 24)
        return cls(proc)

    async def _pump(self) -> None:
        """Relay the child's lines to our stdout; sort replies from the rest."""
        while True:
            raw = await self.proc.stdout.readline()
            if not raw:
                await self.lines.put({"phase": "eof"})
                await self.replies.put({"reply": "eof"})
                return
            try:
                rec = json.loads(raw)
            except ValueError:
                sys.stderr.write(raw.decode("utf-8", "replace"))
                continue
            if "reply" in rec:
                await self.replies.put(rec)
            else:
                say(child=True, **rec)
                self.seen[rec.get("phase")] = rec
                await self.lines.put(rec)

    async def until(self, phase: str, timeout: float) -> dict:
        deadline = time.perf_counter() + timeout
        while True:
            left = deadline - time.perf_counter()
            if left <= 0:
                raise RunFailure(f"the child did not reach {phase!r} in "
                                 f"{timeout:.0f} s")
            rec = await asyncio.wait_for(self.lines.get(), left)
            if rec.get("phase") == phase:
                return rec
            if rec.get("phase") in ("eof", "failed"):
                raise RunFailure("the server child ended before it was "
                                 f"{phase}: {rec.get('error', 'see stderr')}")

    async def ask(self, cmd: str, timeout: float = 120.0) -> dict:
        self.proc.stdin.write((cmd + "\n").encode())
        await self.proc.stdin.drain()
        rec = await asyncio.wait_for(self.replies.get(), timeout)
        if rec.get("reply") != cmd or rec.get("error"):
            raise RunFailure(f"asked the child {cmd!r}, it answered {rec}")
        return rec

    async def stop(self) -> None:
        """Tell the child to quit and wait until it has ended."""
        if self.proc.returncode is None:
            try:
                self.proc.stdin.write(b"quit\n")
                await self.proc.stdin.drain()
                await asyncio.wait_for(self.proc.wait(), 60.0)
            except (asyncio.TimeoutError, ConnectionError, OSError):
                self.proc.kill()
                await self.proc.wait()
        self.pump.cancel()


async def repeat_check(gen: loadgen.LoadGen, chunk: int) -> bool:
    """``correct`` (b): one greedy request sent twice AT ONCE returns the
    same text, and its frames carry text. Sent one after the other, the second
    would take another numeric path (the first one's pages are in the prefix
    cache by then, so its prompt is extended, not prefilled), and on random
    weights the top two logits lie closer than that moves them. Also the HTTP
    path's warm-up."""
    req = traffic_gen.Request(index=10**9, prompt_tokens=96, output_tokens=48,
                              due_s=None)
    now = time.perf_counter()
    a, b = await asyncio.gather(
        gen.generate(req, measured=False, t_due=now, keep_text=True),
        gen.generate(req, measured=False, t_due=now, keep_text=True))
    same = (a.ok and b.ok and a.text == b.text and bool(a.text)
            and not stats.frames_check([a, b], chunk)["short"])
    say(phase="repeat_check", ok=same, eval_count=[a.eval_count, b.eval_count],
        prompt_eval_count=[a.prompt_eval_count, b.prompt_eval_count],
        prompt_tokens_meant=req.prompt_tokens, frames=len(a.frames),
        text_chars=[len(a.text), len(b.text)], errors=[a.error, b.error])
    gen.records.clear()
    return same


def numbers_compared(probe: dict, repeat_ok: bool, resolution_ok: bool,
                     failed: int, short_share: float) -> dict:
    """Every number ``correct`` compared, beside its limit, under a short
    plain name: the probe's readings as the child said them, then what the
    window showed."""
    out = dict(probe)
    out["resolution_differs"] = {"value": int(not resolution_ok), "limit": 0}
    out["repeat_texts_differ"] = {"value": int(not repeat_ok), "limit": 0}
    out["failed_requests"] = {"value": failed, "limit": 0}
    out["short_frames_share"] = {"value": short_share,
                                 "limit": stats.MAX_SHORT_SHARE}
    return out


async def run_cell(args, cell, trace_dir: str) -> int:
    child = await Child.start(cell, args.seed, trace_dir, args.rehearse)
    try:
        ready = await child.until("ready", READY_TIMEOUT_S)
        gen = loadgen.LoadGen(ready["port"], ready["model"], args.seed,
                              ready["prompt_overhead_tokens"])
        repeat_ok = await repeat_check(gen, ready["decode_chunk"])
        correct = (bool(ready["probe_ok"]) and bool(ready["resolution_ok"])
                   and repeat_ok)

        mix, seconds = cell.mix, float(args.seconds)
        rate = cell.numbers.get("rate_rps")
        requests = traffic_gen.make_requests(
            mix, args.seed, max_seq_len=ready["max_seq_len"],
            seconds=seconds, rate_rps=rate)
        ramp = float(mix["ramp_seconds"])
        t0 = time.perf_counter() + ramp
        setup_s = t0 - T_START
        if mix["kind"] == "closed":
            clients = mix["clients"]
            if isinstance(clients, str):
                clients = cell.conf[clients]
            if args.rehearse:
                clients = min(int(clients), ready["max_slots"])
            load = gen.closed_loop(requests, int(clients), t0, seconds,
                                   DRAIN_S)
        else:
            load = gen.open_loop(requests, t0, seconds, DRAIN_S)
        say(phase="window", kind=mix["kind"], seconds=seconds, ramp_s=ramp,
            rate_rps=rate, requests_made=len(requests), setup_s=setup_s)

        scrapes = {}

        async def scrape_at(key: str, t: float) -> None:
            await loadgen.sleep_until(t)
            scrapes[key] = prom.parse(await gen.scrape())

        async def trace_at(t: float, length: float) -> None:
            await scrape_at("trace_before", t)
            await child.ask("trace_start")
            await asyncio.sleep(length)
            await child.ask("trace_stop", 300.0)
            scrapes["trace_after"] = prom.parse(await gen.scrape())
            scrapes["trace_mid"] = t + length / 2.0

        side = [scrape_at("before", t0), scrape_at("after", t0 + seconds)]
        if args.trace:
            length = min(float(mix["trace_seconds"]), seconds / 2.0,
                         0.5 if args.rehearse else 1e9)
            side.append(trace_at(t0 + (seconds - length) / 2.0, length))
        await asyncio.gather(load, *side)

        recs = gen.records
        meas = [r for r in recs if r.measured]
        failed = [r for r in meas if not r.ok]
        say(phase="requests", sent=len(recs), attempted=len(meas),
            finished=len(meas) - len(failed), failed=len(failed),
            errors=sorted({r.error or f"eval_count {r.eval_count} of "
                           f"{r.output_tokens}" for r in failed})[:5],
            in_flight_each_second=loadgen.in_flight_curve(recs, t0, seconds),
            late_ms_max=max(((r.t_sent - r.t_due) * 1e3 for r in meas),
                            default=None))
        frames = stats.frames_check(meas, ready["decode_chunk"])
        say(phase="frames", **frames)
        correct = (correct and not failed and bool(meas)
                   and frames["short_share"] <= stats.MAX_SHORT_SHARE)

        mem = await child.ask("memory")
        trace = (await child.ask("reduce", 300.0)) if args.trace else None
    finally:
        await child.stop()
    if child.proc.returncode != 0:
        raise RunFailure(f"the server child exited {child.proc.returncode}")

    dev = child.seen["device"]
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": max(d["peak_bytes_in_use"]
                                       for d in mem["devices"])}
    if args.trace:
        metrics, extra = layer_metrics(cell, recs, scrapes, trace, ready,
                                       device["kind"], args.rehearse)
        if trace.get("error"):
            raise RunFailure(f"the trace could not be reduced: {trace}")
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        say(phase="trace", **{k: trace[k] for k in (
            "devices", "busy_s", "window_s", "modules", "idle_gap_totals",
            "lines", "trace_bytes") if k in trace}, notes=extra)
    else:
        metrics = stats.reduce_records(
            recs, t0, seconds, [m["name"] for m in cell.end_to_end])
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        say(phase="percentiles", **{
            k: {"n": v.get("n"), "supported": v.get("supported")}
            for k, v in metrics.items() if "n" in v},
            distributions=stats.distributions(recs),
            overlap_tok_s=stats.overlap_tok_s(recs, t0, seconds))
        missing = [m["name"] for m in cell.end_to_end
                   if m["name"] not in metrics]
        if missing:
            raise RunFailure(f"no sample for {missing}")
    result = {"correct": bool(correct), "attempted": len(meas),
              "failed": len(failed),
              "metrics": {k: {"value": v["value"], "unit": v["unit"]}
                          for k, v in metrics.items()},
              "device": device}
    if args.trace:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["compared"] = numbers_compared(
        child.seen["probe_done"]["compared"], repeat_ok,
        bool(ready["resolution_ok"]), len(failed), frames["short_share"])
    for name, pair in result["compared"].items():
        sys.stderr.write(f"compared {name}: {pair['value']} "
                         f"(limit {pair['limit']})\n")
    if args.rehearse:
        say(rehearsal=True, note="CPU, toy widths: not a result",
            would_print=result)
    else:
        say(**result)
    return 0


def layer_metrics(cell, recs, scrapes, trace, ready, device_kind: str,
                  rehearse: bool):
    """Each per-layer metric of the cell from its own reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    peaks = (None if rehearse else work.load_peaks(
        os.path.join(HERE, "peaks.json"), device_kind))
    mid = scrapes.get("trace_mid")
    ctx = types.SimpleNamespace(
        records=recs, before=scrapes["before"], after=scrapes["after"],
        trace_before=scrapes.get("trace_before", {}),
        trace_after=scrapes.get("trace_after", {}), trace=trace,
        resolved=ready, conf=cell.conf, peaks=peaks, notes={},
        live_tokens=(loadgen.live_tokens_at(recs, mid)
                     if mid is not None and peaks else None))
    out = {}
    for m in cell.per_layer:
        mod = layer_reader(m["name"])
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out, ctx.notes


async def amain(args) -> int:
    cell = find_cell(args.workload)
    if not os.path.isdir(os.path.join(REPO, "ollama_operator_tpu")):
        raise RunFailure("the program (ollama_operator_tpu/) is not in this "
                         "checkout: there is nothing to measure")
    say(phase="cell", workload=cell.name, config=cell.conf["name"],
        traffic=cell.mix_name, chips=cell.chips, numbers=cell.numbers,
        seed=args.seed, seconds=args.seconds, trace=args.trace)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        return await run_cell(args, cell, trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    try:
        return asyncio.run(amain(args))
    except RunFailure as e:
        sys.stderr.write(f"benchmark: {e}\n")
        return 1


if __name__ == "__main__":
    sys.exit(main())
