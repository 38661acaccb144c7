"""How unevenly the routers loaded the experts between the two scrapes around
the trace: the most-loaded expert's tokens over the mean over all the
router's experts, less one, in percent (0 = every expert was kept for as many
tokens as any other; the step waits for the busiest). From the program's
``tpu_model_moe_expert_tokens_total{expert}``, which the engine brings off the
device once a decode chunk. None for a program without the counter, or
where no token was routed in between."""
from benchmark import prom

UNIT = "%"
NAME = "tpu_model_moe_expert_tokens_total"


def read(ctx):
    after = {d["expert"]: v for d, v in prom.select(ctx.trace_after, NAME)}
    if not after:
        return None
    before = {d["expert"]: v for d, v in prom.select(ctx.trace_before, NAME)}
    took = [v - before.get(e, 0.0) for e, v in after.items()]
    mean = sum(took) / len(took)
    if mean <= 0:
        return None
    ctx.notes["moe_expert_load"] = dict(
        experts=len(took), tokens=sum(took), most=max(took), least=min(took))
    return 100.0 * (max(took) / mean - 1.0)
