"""Does ``decoder._index_keep`` keep, on the chip, the sets the rule says?

    chiprun -- python3 hack/index_keep_on_chip.py

The rule: of a query's visible positions, the ``index_topk`` of largest
score, a tie on the last place to the earlier position. ``_index_keep`` reads
both the k-th score and the last tied position kept off ``lax.top_k``'s last
place, which holds only where the backend's top-k puts the lower index first
among equal scores. Scores at glm-5's decode shape ([64 slots, 1 query, 4,096
positions], 2,048 kept) in crowds of ties (1, 2, 5 levels: the ReLU leaves
many at 0) and distinct, slots of every depth, against numpy. Prints one JSON
line; exit code 1 on any difference."""
import json
import sys

import jax
import numpy as np

sys.path.insert(0, ".")
from ollama_operator_tpu.models import config as cfglib, decoder

cfg = cfglib.PRESETS["glm-5"]
B, A, K = 64, 4096, cfg.index_topk
keep = jax.jit(lambda s, v: decoder._index_keep(cfg, s, v))
wrong = {}
for levels in (1, 2, 5, 0):
    rng = np.random.default_rng(46 + levels)
    score = (rng.integers(0, levels, (B, 1, A)) if levels
             else rng.standard_normal((B, 1, A))).astype(np.float32)
    q_pos = rng.integers(0, A, (B, 1))
    q_pos[:4, 0] = [K - 2, K - 1, K, A - 1]
    visible = np.arange(A)[None, None, :] <= q_pos[:, :, None]
    want = np.zeros_like(visible)
    for b in range(B):
        seen = np.flatnonzero(visible[b, 0])
        order = seen[np.lexsort((seen, -score[b, 0, seen]))]
        want[b, 0, order[:K]] = True
    got = np.asarray(keep(score, visible))
    wrong[str(levels)] = int((got != want).sum())
print(json.dumps({"backend": jax.default_backend(),
                  "device": jax.devices()[0].device_kind,
                  "shape": [B, 1, A], "kept": K,
                  "positions_that_differ_by_levels": wrong}))
sys.exit(1 if any(wrong.values()) else 0)
