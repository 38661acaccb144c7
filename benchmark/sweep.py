"""Find an open-loop cell's knee: one process, one set-up, the cell's traffic
offered at each of a list of rates in turn, with the system drained between
them, lowest first, until one is past the knee. For each rate it prints the in-flight curve, the tails and the failures.
The knee is the highest rate at which the count of requests in flight does not
grow over the second half of the window; the cell's ``rate_rps`` (in
``benchmark/cells/<cell>.json``) is then set by hand to 0.8 of it, and the
table goes into PERF.md.

    python3 benchmark/sweep.py --workload <cell> [--traffic <mix>] --rates 0.2,0.4,0.8 --seconds 30 --seed 1

``--traffic`` offers another mix's traffic on the cell's configuration: the way
to find the knee of a mix before it has a cell.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import loadgen, stats, traffic_gen  # noqa: E402
from benchmark import run as bench  # noqa: E402


def grows(curve) -> bool:
    """Whether the in-flight count grew over the window's second half: its
    last quarter's mean above the third quarter's by more than a tenth and
    by more than two requests."""
    n = len(curve)
    third = curve[n // 2: 3 * n // 4]
    last = curve[3 * n // 4:]
    a = sum(third) / max(len(third), 1)
    b = sum(last) / max(len(last), 1)
    return b > a * 1.1 and b > a + 2


async def sweep(args) -> int:
    cell = bench.find_cell(args.workload)
    if args.traffic:        # a mix that has no cell yet, on a cell's config
        cell.mix_name = args.traffic
        cell.mix = traffic_gen.load_mix(args.traffic)
    if cell.mix["kind"] != "open":
        raise bench.RunFailure("only an open-loop cell has a knee to find")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    child = await bench.Child.start(cell, args.seed, trace_dir, args.rehearse)
    try:
        ready = await child.until("ready", bench.READY_TIMEOUT_S)
        gen = loadgen.LoadGen(ready["port"], ready["model"], args.seed,
                              ready["prompt_overhead_tokens"])
        await bench.repeat_check(gen, ready["decode_chunk"])
        for rate in args.rates:
            gen.records.clear()
            reqs = traffic_gen.make_requests(
                cell.mix, args.seed, max_seq_len=ready["max_seq_len"],
                seconds=args.seconds, rate_rps=rate)
            t0 = time.perf_counter() + float(cell.mix["ramp_seconds"])
            await gen.open_loop(reqs, t0, args.seconds, bench.DRAIN_S * 2)
            meas = [r for r in gen.records if r.measured]
            failed = [r for r in meas if not r.ok]
            curve = loadgen.in_flight_curve(gen.records, t0, args.seconds)
            m = stats.reduce_records(
                gen.records, t0, args.seconds,
                ["ttft_p50_ms", "ttft_p95_ms", "stream_gap_p95_ms"])
            over = grows(curve) or bool(failed)
            bench.say(phase="sweep", rate_rps=rate, attempted=len(meas),
                      failed=len(failed), in_flight=curve, grows=over,
                      errors=sorted({r.error for r in failed})[:3],
                      **{k: round(v["value"], 1) for k, v in m.items()})
            if over:        # past the knee: what follows would start on
                break       # the backlog this rate left behind
    finally:
        await child.stop()
        shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--traffic", default="")
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    try:
        return asyncio.run(sweep(args))
    except bench.RunFailure as e:
        sys.stderr.write(f"sweep: {e}\n")
        return 1


if __name__ == "__main__":
    sys.exit(main())
