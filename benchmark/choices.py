"""What the path under test chose, brought out of its compiled program.

A *choice* is "keep k of n by score" made inside the forward pass: the experts
a router keeps. Two sound computations of one model in two precisions make
different choices where two scores lie closer than rounding moves them, and
everything downstream then differs by a whole member's output. So ``correct``
does not ask the path under test to choose as the float32 reference does: it
takes the path's own sets, hands them to the reference (``forward_chosen``, the
contract at the head of ``server_child.py``) and asks two things, that no
chosen member lies far below the reference's k-th best, and that the logits
under those sets agree.

The program has no recorder of its own yet (PERF.md section 7: this PR may not
add one), so the benchmark taps the one choice site it has,
``models/decoder.py:_moe_gates`` (device scope ``moe.route``), from outside:
for as long as the block is open the function is wrapped, and every call traced
inside the block sends its sets to the host through an ordered callback, which
is the way out of a ``lax.scan`` over the layers. Outside a block nothing is
wrapped and the program traces as it always did: ``jax.jit`` keeps a function's
trace, so what is jitted inside a block is a function made for that block (the
probe's ``logits_fn``), never one the served engine also runs. A program that
renames the function hands out no set, which fails the probe of a reference
that has ``forward_chosen``.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Iterator, List

import numpy as np

SITE = "moe.route"      # the program's device scope around its router


class Choices:
    """Sets by traced call of the site, in the order the calls were traced;
    each call's sets arrive once a layer, in layer order."""

    def __init__(self) -> None:
        self._got: List[List[np.ndarray]] = []

    def _open(self) -> int:
        self._got.append([])
        return len(self._got) - 1

    def _take(self, call: int, sets) -> None:
        self._got[call].append(np.sort(np.asarray(sets), axis=-1))

    def calls(self) -> List[np.ndarray]:
        """One [L, N, k] array (members ascending) for each traced call of
        the site that has run since this was last asked, in trace order."""
        import jax
        jax.effects_barrier()
        out = []
        for c in self._got:
            if c:
                out.append(np.stack(c))
                c.clear()
        return out


@contextlib.contextmanager
def record_choices() -> Iterator[Choices]:
    """Collect the sets the router hands out in every program traced inside
    the block, when that program runs."""
    import jax
    from jax import lax

    from ollama_operator_tpu.models import decoder

    chosen = Choices()
    inner = decoder._moe_gates

    @functools.wraps(inner)
    def tapped(cfg, lp, xf):
        gates = inner(cfg, lp, xf)      # [N, E], zero for experts not kept
        jax.debug.callback(
            functools.partial(chosen._take, chosen._open()),
            lax.top_k(gates, cfg.n_experts_used)[1], ordered=True)
        return gates

    decoder._moe_gates = tapped
    try:
        yield chosen
    finally:
        decoder._moe_gates = inner
