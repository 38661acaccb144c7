"""trace-env fixture: outside the traced packages a read is the
knob-registry pass's business, not this one's."""

import os

PAGED = os.environ.get("TPU_PAGED", "")
