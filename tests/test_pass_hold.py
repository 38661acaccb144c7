"""The admission pass and the launch behind it are held until the chunk in
flight is about to land (Scheduler._hold_pass, _hold_until).

Everything here runs on a clock the test owns: the scheduler reads
``_now`` and sleeps on ``_wake``, and ``Clock`` is both, so a "sleep" moves
the clock to the next scripted arrival or to the timeout's end and returns.
The engine is a real tiny one; the chunk in flight is wrapped in a handle
that lands when the test says (``Held``), its begin, the last chunk's time
and the pass's cost are set in the clock's seconds. Nothing sleeps.

The invariants under test:
- a request submitted after the fan-out and before the deadline is admitted
  by THAT step's pass and rides the chunk launched behind it;
- the hold ends at once when every free slot has its waiter (filled), at
  the deadline otherwise (deadline), and the launch is made before the
  chunk in flight lands;
- arrivals of one bucket that came during one hold share one admit_many;
- a cancelled or expired waiter is no waiter;
- nothing is held with no chunk in flight, no free slot, no measured chunk
  or pass yet, in a synchronous loop, or beside a host-masked slot;
- tpu_model_pass_holds_total{end} and
  tpu_model_decode_launches_total{timing} count as their help says.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.runtime.engine import Engine
from ollama_operator_tpu.runtime.scheduler import Scheduler
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

from test_admit_launch import (ECFG, GREEDY, TINY, clean, frames, manual,
                               prompt, tokens_of)

CHUNK = 10.0        # what the last chunk took, in the clock's seconds
COST = 1.0          # what the host takes to hand a step's first program over
ENDS = ("filled", "deadline", "none")
TIMINGS = ("ahead", "late", "empty")


@pytest.fixture(scope="module")
def eng():
    params = decoder.init_params(TINY, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    return Engine(TINY, params, ecfg=ECFG)          # four slots


class Clock:
    """The hold's clock and the event it sleeps on. ``wait`` never blocks:
    it moves the clock to the earliest scripted event inside the timeout
    and runs it (a submit sets the event, as on a live server), else to
    the timeout's end."""

    def __init__(self):
        self.t0 = self.t = time.perf_counter()
        self.script = []          # [(t, fn)]
        self.waits = []           # every timeout asked for
        self._set = False

    def now(self):
        return self.t

    def at(self, dt, fn):
        self.script.append((self.t0 + dt, fn))

    def set(self):
        self._set = True

    def clear(self):
        self._set = False

    def is_set(self):
        return self._set

    def wait(self, timeout=None):
        self.waits.append(timeout)
        if self._set:
            return True
        due = sorted((e for e in self.script if e[0] <= self.t + timeout),
                     key=lambda e: e[0])
        if due:
            self.script.remove(due[0])
            self.t = max(self.t, due[0][0])
            due[0][1]()
            return self._set
        self.t += timeout
        return False


class Held:
    """A program in flight (the chunk; a pass's prefill), landing when the
    test says or when waited for: everything else is the real handle's."""

    def __init__(self, handle, log, waited="wait"):
        self._h, self._log, self._waited = handle, log, waited
        self.landed = False

    def ready(self):
        return self.landed

    def wait(self):
        self._log.append(self._waited)
        self.landed = True
        return self._h.wait()

    def __getattr__(self, name):
        return getattr(self._h, name)


def ends():
    return {e: METRICS.get("tpu_model_pass_holds_total", f'{{end="{e}"}}')
            for e in ENDS}


def timings():
    return {t: METRICS.get("tpu_model_decode_launches_total",
                           f'{{timing="{t}"}}') for t in TIMINGS}


def moved(before, now):
    return {k: int(v - before[k]) for k, v in now.items() if v != before[k]}


def in_flight(eng, monkeypatch, running, **sched_kw):
    """A scheduler stepped by hand, ``running`` requests decoding, their
    first chunk in flight and wrapped in ``Held``; the clock installed, the
    chunk begun at the clock's start, two chunks of CHUNK seconds and a lead of
    COST measured. Returns (scheduler, clock, log, requests)."""
    sched_kw.setdefault("async_dispatch", True)
    sched = manual(Scheduler(eng, prefill_chunk=0, **sched_kw))
    reqs = [sched.submit(prompt(9 + i, base=5 * i), GREEDY, max_tokens=64)
            for i in range(running)]
    sched._step()
    log = []
    clock = Clock()
    sched._now, sched._wake = clock.now, clock
    sched._stop.clear()       # manual() set it; a shutdown ends a hold
    if sched._pending is not None:
        handle, snapshot = sched._pending
        sched._pending = (Held(handle, log), snapshot)
    eng._t_landed = clock.t0
    sched._chunk_s.extend([CHUNK, CHUNK])
    sched._lead_s = COST
    launch = eng.decode_n_launch

    def spy(*a, **kw):
        log.append("launch")
        return launch(*a, **kw)
    monkeypatch.setattr(eng, "decode_n_launch", spy)
    for name in ("admit_launch", "admit_many_launch"):
        real = getattr(eng, name)
        monkeypatch.setattr(eng, name, lambda *a, _n=name, _r=real, **kw: (
            log.append(_n), Held(_r(*a, **kw), log, "collect"))[1])
    return sched, clock, log, reqs


def done(sched, eng):
    sched.shutdown()
    clean(eng)


# ------------------------------------------------------------ the tentpole

def test_an_arrival_inside_the_hold_rides_the_next_chunk(eng, monkeypatch):
    """Two of four slots free, one arrival three seconds into a ten second
    chunk: the pass waits for it and then, one waiter short, to the
    deadline (nine: the chunk's end less a pass); the request is admitted
    by this step and stands in the snapshot of the chunk launched behind
    it, and that launch is made before the chunk in flight is waited for."""
    sched, clock, log, _ = in_flight(eng, monkeypatch, running=2)
    try:
        late = []
        clock.at(3.0, lambda: late.append(
            sched.submit(prompt(11, base=2), GREEDY, max_tokens=5)))
        before, launched = ends(), timings()
        sched._step()
        assert clock.t == pytest.approx(clock.t0 + CHUNK - COST)
        assert clock.waits == [pytest.approx(9.0), pytest.approx(6.0)]
        assert moved(before, ends()) == {"deadline": 1}
        (req,) = late
        assert req.slot is not None and sched._running[req.slot] is req
        assert sched._pending[1][req.slot] is req
        assert log == ["admit_launch", "launch", "wait", "collect"]
        assert moved(launched, timings()) == {"ahead": 1}
        assert tokens_of(frames(req))            # its first token came
    finally:
        done(sched, eng)


def test_the_hold_ends_when_every_free_slot_has_its_waiter(eng,
                                                           monkeypatch):
    """Both successors come (2 s, 3 s): the hold ends with the second, far
    from the deadline, and the two, of one bucket, share ONE admit_many."""
    sched, clock, log, _ = in_flight(eng, monkeypatch, running=2)
    try:
        late = []
        for dt, base in ((2.0, 2), (3.0, 9)):
            clock.at(dt, lambda b=base: late.append(
                sched.submit(prompt(11, base=b), GREEDY, max_tokens=5)))
        before = ends()
        sched._step()
        assert clock.t == pytest.approx(clock.t0 + 3.0)
        assert moved(before, ends()) == {"filled": 1}
        assert log == ["admit_many_launch", "launch", "wait", "collect"]
        assert all(sched._pending[1][r.slot] is r for r in late)
        assert sched.n_active == 4
    finally:
        done(sched, eng)


@pytest.mark.parametrize("waiter", ["live", "cancelled", "expired"])
def test_a_cancelled_or_expired_waiter_is_no_waiter(eng, monkeypatch,
                                                    waiter):
    """Two free slots and two requests waiting when the step begins:
    nothing to hold for. With one of the two cancelled or past its
    deadline a slot is short again: the dead one gets its frame, the hold
    runs to the deadline and the pass admits the live one."""
    sched, clock, _, _ = in_flight(eng, monkeypatch, running=2)
    try:
        live = sched.submit(prompt(11, base=2), GREEDY, max_tokens=5)
        other = sched.submit(
            prompt(11, base=9), GREEDY, max_tokens=5,
            deadline_s=1e-9 if waiter == "expired" else None)
        if waiter == "cancelled":
            other.cancel()
        before = ends()
        sched._step()
        if waiter == "live":
            assert clock.waits == []
            assert moved(before, ends()) == {"none": 1}
            assert other.slot is not None
        else:
            assert clock.waits == [pytest.approx(CHUNK - COST)]
            assert moved(before, ends()) == {"deadline": 1}
            assert other.slot is None
            kind = frames(other)[-1][0]
            assert kind == ("done" if waiter == "cancelled" else "shed")
        assert live.slot is not None
    finally:
        done(sched, eng)


# ------------------------------------------------------- nothing is held

def _no_chunk(sched, eng):
    sched._pending = None


def _no_measured_chunk(sched, eng):
    sched._chunk_s.clear()


def _one_measured_chunk(sched, eng):
    sched._chunk_s.clear()
    sched._chunk_s.append(CHUNK)      # one alone may hold a compile


def _no_measured_pass(sched, eng):
    sched._lead_s = None


def _host_masked(sched, eng):
    # a device-grammar slot whose automaton left the device's table while
    # its next chunk was already launched: host-masked from now on
    req = next(r for r in sched._running if r is not None)
    req.constraint = object()


@pytest.mark.parametrize("why,counted", [
    (_no_chunk, {}), (_no_measured_chunk, {"none": 1}),
    (_one_measured_chunk, {"none": 1}),
    (_no_measured_pass, {"none": 1}), (_host_masked, {"none": 1})],
    ids=["no_chunk_in_flight", "no_measured_chunk", "one_measured_chunk",
         "no_measured_pass", "a_host_masked_slot"])
def test_nothing_is_held_without_its_conditions(eng, monkeypatch, why,
                                                counted):
    """Two slots free and nobody waiting, which would hold, but for one
    condition each: no sleep, and the counter says none (or nothing: a
    step with no chunk in flight is no held pass at all)."""
    sched, clock, _, _ = in_flight(eng, monkeypatch, running=2)
    try:
        why(sched, eng)
        before = ends()
        sched._hold_pass()
        assert clock.waits == []
        assert moved(before, ends()) == counted
    finally:
        for r in sched._running:
            if r is not None:
                r.constraint = None
        done(sched, eng)


def test_nothing_is_held_with_no_free_slot(eng, monkeypatch):
    sched, clock, log, _ = in_flight(eng, monkeypatch, running=4)
    try:
        before = ends()
        sched._step()
        assert clock.waits == []
        assert moved(before, ends()) == {"none": 1}
        assert log == ["launch", "wait"]
    finally:
        done(sched, eng)


def test_a_synchronous_loop_holds_nothing(eng, monkeypatch):
    """No chunk is ever in flight when its step begins."""
    sched, clock, _, reqs = in_flight(eng, monkeypatch, running=2,
                                      async_dispatch=False)
    try:
        before = ends()
        for _ in range(3):
            sched._step()
        assert sched._pending is None and clock.waits == []
        assert moved(before, ends()) == {}
        assert all(len(tokens_of(frames(r))) > 4 for r in reqs)
    finally:
        done(sched, eng)


def test_a_deadline_already_past_is_counted_and_not_slept(eng, monkeypatch):
    """A lead longer than a chunk (a pass that drains a paged pool for
    pages before its first launch has one) leaves no room: the step goes on
    as it always did."""
    sched, clock, log, _ = in_flight(eng, monkeypatch, running=2)
    try:
        sched._lead_s = CHUNK + 1.0
        before = ends()
        sched._step()
        assert clock.waits == [] and clock.t == clock.t0
        assert moved(before, ends()) == {"deadline": 1}
        assert log == ["launch", "wait"]
    finally:
        done(sched, eng)


# ------------------------------------------------ what the step measures

def test_the_lead_is_the_largest_of_recent_steps(eng, monkeypatch):
    """Each double-buffered step measures housekeeping to its first program
    handed to the runtime: the decode launch, or the pass's first prefill
    where it admitted (the rest of a pass runs in that one's shadow, and may
    block on the runtime's queue). An older reading counts a tenth less a
    step, a larger one replaces it."""
    sched, _, log, _ = in_flight(eng, monkeypatch, running=2)
    try:
        sched._lead_s = 100.0
        sched._step()
        assert sched._lead_s == pytest.approx(90.0)
        sched._lead_s = 1e-9
        t0 = time.perf_counter()
        sched._step()
        handle = sched._pending[0]
        # this step's own reading: housekeeping to the launch's hand-over
        assert 1e-9 < sched._lead_s <= handle.t_queued - t0
        # a pass that admits: its FIRST prefill's hand-over is the reading,
        # not the second's nor the decode launch's behind them
        real = eng.admit_launch
        firsts = []

        def spy(*a, **kw):
            out = real(*a, **kw)
            firsts.append(out.t_queued)
            return out
        monkeypatch.setattr(eng, "admit_launch", spy)
        for n in (11, 40):                       # two buckets: two singles
            sched.submit(prompt(n, base=3), GREEDY, max_tokens=4)
        sched._lead_s = 1e-9
        t0 = time.perf_counter()
        sched._step()
        assert len(firsts) == 2 and firsts[0] < firsts[1]
        assert 1e-9 < sched._lead_s <= firsts[0] - t0
    finally:
        done(sched, eng)


def test_the_lead_leaves_out_the_runtimes_launch_call(eng, monkeypatch):
    """The runtime holds a launch while 32 programs are in flight, and
    t_queued is stamped after that wait: a lead that held it would end
    every later hold at once. What a step spends inside the runtime's
    call (Engine.enqueue_s) is not the host's."""
    sched, _, _, _ = in_flight(eng, monkeypatch, running=2)
    try:
        held = 0.05
        enqueue = eng._enqueue

        def blocked(program, exe, *args):
            return enqueue(program,
                           lambda *a: (time.sleep(held), exe(*a))[1], *args)
        monkeypatch.setattr(eng, "_enqueue", blocked)
        sched._lead_s = 1e-9
        t0, clock0 = time.perf_counter(), eng.enqueue_s
        sched._step()
        handle = sched._pending[0]
        assert eng.enqueue_s - clock0 >= held
        assert handle.t_queued - t0 >= held
        assert 1e-9 < sched._lead_s <= handle.t_queued - t0 - held
    finally:
        done(sched, eng)


def test_the_lead_does_not_learn_from_a_step_that_drained(eng, monkeypatch):
    """A step that stalled for pages waited the chunk in flight out before
    its first launch (_stall_for_pages drains _pending): what it took is
    the drain, not the host's lead. 0.3 s learnt there would keep the
    hold off for some 27 steps."""
    sched, _, log, _ = in_flight(eng, monkeypatch, running=2)
    try:
        relieve = sched._relieve_pressure

        def dry_once(n_steps):
            sched._stall_for_pages("pool_dry_decode")
            return relieve(n_steps)
        monkeypatch.setattr(sched, "_relieve_pressure", dry_once)
        sched._lead_s = 0.5
        sched._step()
        assert log == ["wait", "launch"]         # drained, then launched
        assert sched._lead_s == 0.5              # not even decayed
    finally:
        done(sched, eng)


def test_the_chunk_in_flight_is_taken_for_the_shorter_of_the_last_two(
        eng, monkeypatch):
    """Every landed decode chunk is recorded as the engine accounts it; a
    chunk that held a compile or a stall (sixty seconds here) lengthens no
    hold: the deadline stays the ordinary chunk's."""
    sched, clock, _, _ = in_flight(eng, monkeypatch, running=2)
    try:
        sched._chunk_s.append(60.0)
        assert sched._hold_until() == pytest.approx(
            clock.t0 + CHUNK - COST)
        sched._step()
        assert clock.t == pytest.approx(clock.t0 + CHUNK - COST)
        handle = sched._pending[0]
        sched._step()
        assert list(sched._chunk_s)[-1] == pytest.approx(
            handle.t_done - handle.t_begin)
    finally:
        done(sched, eng)


@pytest.mark.parametrize("timing", TIMINGS)
def test_a_launch_is_counted_by_what_the_device_held(eng, monkeypatch,
                                                     timing):
    """ahead: the chunk in flight had not landed when its successor was
    launched; late: it had; empty: nothing was in flight (the first
    chunk). Asked of the handle, which syncs nothing."""
    if timing == "empty":
        sched = manual(Scheduler(eng, prefill_chunk=0, async_dispatch=True))
        sched.submit(prompt(9), GREEDY, max_tokens=8)
    else:
        sched, _, _, _ = in_flight(eng, monkeypatch, running=4)
        sched._pending[0].landed = timing == "late"
    try:
        before = timings()
        sched._step()
        assert moved(before, timings()) == {timing: 1}
    finally:
        done(sched, eng)


@pytest.mark.parametrize("kind", ["decode", "admit"])
def test_a_handle_says_whether_it_has_landed_without_a_fetch(eng,
                                                             monkeypatch,
                                                             kind):
    fetches = []
    fetch = eng._fetch
    monkeypatch.setattr(eng, "_fetch",
                        lambda x: (fetches.append(1), fetch(x))[1])
    try:
        admit = eng.admit_launch(0, prompt(9), GREEDY)
        handle = admit if kind == "admit" else eng.decode_n_launch()
        assert handle.ready() in (True, False) and fetches == []
        handle._toks.block_until_ready()
        assert handle.ready() and fetches == []
        admit.wait()
        handle.wait()
        assert handle.ready()
    finally:
        clean(eng)


# ------------------------------------------- the benchmark's two readers

@pytest.mark.parametrize("reader,family,label,counted,want", [
    ("pass_filled_share", "tpu_model_pass_holds_total", "end", None, None),
    ("pass_filled_share", "tpu_model_pass_holds_total", "end",
     {"none": 31}, None),
    ("pass_filled_share", "tpu_model_pass_holds_total", "end",
     {"filled": 60, "deadline": 20, "none": 31}, 75.0),
    ("late_launch_share", "tpu_model_decode_launches_total", "timing",
     None, None),
    ("late_launch_share", "tpu_model_decode_launches_total", "timing",
     {"empty": 88}, None),
    ("late_launch_share", "tpu_model_decode_launches_total", "timing",
     {"ahead": 97, "late": 3}, 3.0)])
def test_a_reader_reads_the_windows_counts_of_the_real_registry(
        reader, family, label, counted, want):
    """Two scrapes of the real registry's text: the window's counts alone;
    nothing, and no raise, where the program has no such counter (the
    parent) or counted nothing the share is made of."""
    import types

    from benchmark import prom, run
    from ollama_operator_tpu.server.metrics import Metrics
    reg = Metrics()
    reg.inc("tpu_model_generated_tokens_total", 5.0)
    values = ENDS if label == "end" else TIMINGS
    if counted is not None:
        for i, v in enumerate(values):
            reg.inc(family, float(i), f'{{{label}="{v}"}}')
    before = prom.parse(reg.render())
    for v, n in (counted or {}).items():
        reg.inc(family, float(n), f'{{{label}="{v}"}}')
    ctx = types.SimpleNamespace(before=before,
                                after=prom.parse(reg.render()))
    got = run.layer_reader(reader).read(ctx)
    assert got == (None if want is None else pytest.approx(want))
