"""Mean time of an admission dispatch's part "run":
what the dispatch itself took, from the later of its launch's end and its
predecessor's tokens reaching the host to its own (host fetch times, so the
first dispatch a pass collects also holds the time its token lay on the
device until the host came for it). Sum over count of the program's
``tpu_model_admit_dispatch_seconds{part="run"}``, observed once a dispatch
(an ``admit_many`` of m once) when its first token reaches the host. The
three parts add up to the mean of launch-to-first-token, the stage
``ttft_prefill_p90_ms`` reads. Nothing to read from a program without the
histogram, nor where no admission landed."""
from benchmark import admission_pass

UNIT = "ms"


def read(ctx):
    return admission_pass.part_ms(ctx, "run")
