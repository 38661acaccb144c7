"""Share of the admission passes that had to stall for pages: the pass found
the paged pool dry with a chunk in flight or pages fenced behind one, landed
and fanned that chunk out with the device running dry, unfenced, and only
then admitted (the program's ``tpu_model_admission_passes_total{stalled}``:
yes / (yes + no), one count a pass that took a request off the waiting
line). 0 on a contiguous cache. Nothing to read from a program without the
counter, nor where no pass admitted anyone."""
from benchmark import admission_pass

UNIT = "%"


def read(ctx):
    both = admission_pass.passes(ctx)
    if both is None or not both[1]:
        return None
    ctx.notes["admission_passes"] = dict(stalled=both[0], passes=both[1])
    return 100.0 * both[0] / both[1]
