"""Mean time of an admission dispatch's part "launch":
from the launch's begin until the program was handed to the runtime: the host's
staging (the key install, the uploads) and the call into the runtime, all of
the wait included where the runtime held the launch because its queue was
full. Sum over count of the program's
``tpu_model_admit_dispatch_seconds{part="launch"}``, observed once a dispatch
(an ``admit_many`` of m once) when its first token reaches the host. The
three parts add up to the mean of launch-to-first-token, the stage
``ttft_prefill_p90_ms`` reads. Nothing to read from a program without the
histogram, nor where no admission landed."""
from benchmark import admission_pass

UNIT = "ms"


def read(ctx):
    return admission_pass.part_ms(ctx, "launch")
