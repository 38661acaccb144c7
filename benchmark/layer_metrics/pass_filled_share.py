"""Share of the admission passes the scheduler held back, with a decode
chunk in flight and free slots nobody waited for yet, whose hold ended
because every free slot had got its waiter and not because the chunk in
flight was about to land (the program's ``tpu_model_pass_holds_total{end}``:
filled / (filled + deadline); passes with nothing to hold for, end="none",
are left out). Near 100 where every finisher has a successor close behind
(closed loop, clients = slots); low where successors still miss the pass, or
under open-loop load, where the deadline is what ends a hold. Nothing to
read from a program without the counter, nor where no pass was held."""
from benchmark import prom

UNIT = "%"


def read(ctx):
    filled = prom.delta(ctx.before, ctx.after,
                        "tpu_model_pass_holds_total", end="filled")
    deadline = prom.delta(ctx.before, ctx.after,
                          "tpu_model_pass_holds_total", end="deadline")
    if filled is None or deadline is None:
        return None
    held = filled + deadline
    return 100.0 * filled / held if held else None
