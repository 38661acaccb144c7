"""Mean time of an admission dispatch's part "behind":
queued on the device behind what was launched before it: the chunk in flight
and the pass's earlier prefills (nothing where its predecessor's tokens had
reached the host already). Sum over count of the program's
``tpu_model_admit_dispatch_seconds{part="behind"}``, observed once a dispatch
(an ``admit_many`` of m once) when its first token reaches the host. The
three parts add up to the mean of launch-to-first-token, the stage
``ttft_prefill_p90_ms`` reads. Nothing to read from a program without the
histogram, nor where no admission landed."""
from benchmark import admission_pass

UNIT = "ms"


def read(ctx):
    return admission_pass.part_ms(ctx, "behind")
