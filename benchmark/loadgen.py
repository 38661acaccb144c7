"""The load generator: one process, one thread, one asyncio loop, raw sockets.
It sends ``/api/generate`` requests as a traffic mix says (closed loop: each
client sends its next request the moment the last one ends; open loop: each
request at its due time, whatever the server is doing), stamps every frame
with ``time.perf_counter`` as it arrives, and keeps one ``stats.Record`` a
request.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Callable, Dict, List, Optional, Tuple

from benchmark import traffic_gen
from benchmark.stats import Record
from benchmark.traffic_gen import Request

HOST = "127.0.0.1"
REQUEST_TIMEOUT_S = 120.0


async def http(port: int, method: str, path: str, body: Optional[dict] = None,
               on_line: Optional[Callable[[bytes], None]] = None,
               on_status: Optional[Callable[[int], None]] = None
               ) -> Tuple[int, bytes]:
    """One HTTP/1.1 exchange on its own connection. A chunked body is handed
    to ``on_line`` chunk by chunk as it arrives (the server writes one NDJSON
    frame a chunk); any other body is returned whole. ``on_status`` hears the
    status as soon as its line is read."""
    reader, writer = await asyncio.open_connection(HOST, port)
    try:
        data = b"" if body is None else json.dumps(body).encode()
        head = (f"{method} {path} HTTP/1.1\r\nHost: {HOST}\r\n"
                f"Connection: close\r\nContent-Type: application/json\r\n"
                f"Content-Length: {len(data)}\r\n\r\n").encode()
        writer.write(head + data)
        await writer.drain()
        status_line = await reader.readline()
        if not status_line:
            raise ConnectionError("the server closed the connection")
        status = int(status_line.split()[1])
        if on_status is not None:
            on_status(status)
        headers: Dict[str, str] = {}
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            k, _, v = line.decode("latin-1").partition(":")
            headers[k.strip().lower()] = v.strip()
        if headers.get("transfer-encoding", "").lower() == "chunked":
            rest = bytearray()
            while True:
                size = int((await reader.readline()).strip() or b"0", 16)
                if size == 0:
                    break
                chunk = await reader.readexactly(size)
                await reader.readexactly(2)
                if on_line is not None:
                    on_line(chunk)
                else:
                    rest.extend(chunk)
            return status, bytes(rest)
        n = int(headers.get("content-length", "0"))
        return status, (await reader.readexactly(n) if n
                        else await reader.read())
    finally:
        writer.close()


class LoadGen:
    def __init__(self, port: int, model: str, seed: int, overhead: int):
        self.port, self.model, self.seed = port, model, seed
        self.overhead = overhead
        self.records: List[Record] = []

    async def generate(self, req: Request, *, measured: bool, t_due: float,
                       output_tokens: Optional[int] = None,
                       keep_text: bool = False) -> Record:
        """Send one greedy streaming request and record what came back. Never
        raises for what the server does: the record carries the error."""
        n_out = output_tokens or req.output_tokens
        rec = Record(index=req.index, measured=measured, t_due=t_due,
                     t_sent=time.perf_counter(),
                     prompt_tokens=req.prompt_tokens, output_tokens=n_out)
        self.records.append(rec)
        text: List[str] = []

        def on_line(chunk: bytes) -> None:
            now = time.perf_counter()
            for line in chunk.splitlines():
                if not line.strip():
                    continue
                frame = json.loads(line)
                if frame.get("error"):
                    rec.error = str(frame["error"])
                elif frame.get("done"):
                    rec.t_done = now
                    rec.eval_count = int(frame.get("eval_count") or 0)
                    rec.prompt_eval_count = int(
                        frame.get("prompt_eval_count") or 0)
                    rec.done_reason = str(frame.get("done_reason") or "")
                elif frame.get("response"):
                    rec.frames.append(now)
                    if keep_text:
                        text.append(frame["response"])

        body = {"model": self.model, "raw": True, "stream": True,
                "prompt": traffic_gen.prompt_text(
                    req.prompt_tokens, self.overhead, self.seed, req.index),
                "options": {"num_predict": n_out, "temperature": 0}}
        def on_status(code: int) -> None:
            # now, not when the exchange returns: a request whose last frame
            # has come may be cancelled before its connection has closed
            rec.status = code

        try:
            _status, rest = await asyncio.wait_for(
                http(self.port, "POST", "/api/generate", body, on_line,
                     on_status), REQUEST_TIMEOUT_S)
            if rec.status != 200:
                rec.error = rest.decode("utf-8", "replace")[:200] or "http"
        except asyncio.TimeoutError:
            rec.error = "timeout"
        except (OSError, ValueError, asyncio.IncompleteReadError) as e:
            rec.error = f"{type(e).__name__}: {e}"
        if keep_text:
            rec.text = "".join(text)    # only the repeat check reads this
        return rec

    async def scrape(self) -> str:
        _status, body = await http(self.port, "GET", "/metrics")
        return body.decode()

    async def closed_loop(self, requests: List[Request], clients: int,
                          t0: float, seconds: float, drain_s: float) -> None:
        """``clients`` clients from now: the ramp until ``t0``, the window
        until ``t0 + seconds``. Requests sent inside the window are measured.
        The clients keep the load on after the window until every measured
        request has ended, or ``drain_s`` has passed."""
        t1 = t0 + seconds
        todo = iter(requests)
        stop = asyncio.Event()

        async def client(first: bool) -> None:
            while not stop.is_set():
                req = next(todo, None)
                if req is None:
                    raise RuntimeError("the mix's pool of requests ran out: "
                                       "raise `pool` in the traffic file")
                now = time.perf_counter()
                cut = (traffic_gen.ramp_output(req, self.seed) if first
                       else None)
                first = False
                rec = await self.generate(req, measured=t0 <= now < t1,
                                          t_due=now, output_tokens=cut)
                if not rec.ok:      # a refusing server is not spun against
                    await asyncio.sleep(0.1)

        tasks = [asyncio.ensure_future(client(True)) for _ in range(clients)]
        await sleep_until(t1)
        deadline = t1 + drain_s
        while time.perf_counter() < deadline and any(
                r.measured and r.t_done is None and not r.error
                for r in self.records):
            _raise_failed(tasks)
            await asyncio.sleep(0.05)
        stop.set()
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        _raise_failed(tasks)

    async def open_loop(self, requests: List[Request], t0: float,
                        seconds: float, drain_s: float) -> None:
        """Every request at ``t0 + due_s`` (those due before ``t0`` are the
        ramp). After the window, wait for the measured ones to end, at most
        ``drain_s``."""
        tasks = []
        for req in requests:
            due = t0 + req.due_s
            await sleep_until(due)
            tasks.append(asyncio.ensure_future(self.generate(
                req, measured=req.due_s >= 0.0, t_due=due)))
        await sleep_until(t0 + seconds)
        _done, pending = await asyncio.wait(tasks, timeout=drain_s)
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)


async def sleep_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)


def _raise_failed(tasks) -> None:
    for t in tasks:
        if t.done() and not t.cancelled() and t.exception() is not None:
            raise t.exception()


def live_tokens_at(records: List[Record], t: float) -> float:
    """Positions the in-flight requests held in the cache at time ``t``: the
    prompt once the first frame has come, plus the output's share by the time
    elapsed between its first frame and its end."""
    live = 0.0
    for r in records:
        if not r.frames or r.t_done is None or not r.frames[0] <= t < r.t_done:
            continue
        share = (t - r.frames[0]) / max(r.t_done - r.frames[0], 1e-9)
        live += r.prompt_tokens + share * r.eval_count
    return live


def in_flight_curve(records: List[Record], t0: float, seconds: float,
                    step_s: float = 1.0) -> List[int]:
    """Requests sent and not yet ended, sampled every ``step_s`` over the
    window (an unfinished request counts to the end)."""
    out = []
    k = 0
    while k * step_s <= seconds:
        t = t0 + k * step_s
        out.append(sum(1 for r in records if r.t_due <= t
                       and (r.t_done is None or r.t_done > t)))
        k += 1
    return out
