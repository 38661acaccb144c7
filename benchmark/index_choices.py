"""What latent attention's indexer kept, brought out of the compiled program
as ``choices.py`` brings out the router's sets, for the reference's
``forward_chosen`` (site ``attn.index``: [layers, T, index_topk] int32, -1
where a position has fewer).

The program has one site where the indexer chooses,
``models/decoder.py:_index_keep`` (device scope ``attn.index``), called only
where the attended length passes ``index_topk``; below it nothing is chosen
and a position keeps every earlier one (``keep_all``). For as long as the block
is open the function is wrapped and every call traced inside it sends its
mask [B, T, A] to the host through an ordered callback. Outside a block
nothing is wrapped. A program without the function (the parent's) hands out
nothing."""

from __future__ import annotations

import contextlib
import functools
from typing import Iterator, List

import numpy as np

SITE = "attn.index"


class Kept:
    """Masks in the order they arrived: one [B, T, A] bool array for each
    layer (and each block of queries) of each run of a tapped program."""

    def __init__(self) -> None:
        self._got: List[np.ndarray] = []

    def _take(self, keep) -> None:
        self._got.append(np.asarray(keep))

    def masks(self) -> List[np.ndarray]:
        """Every mask that arrived since this was last asked."""
        import jax
        jax.effects_barrier()
        out, self._got = self._got, []
        return out


@contextlib.contextmanager
def record_index() -> Iterator[Kept]:
    """Collect the masks the indexer hands out in every program traced
    inside the block, when that program runs."""
    import jax

    from ollama_operator_tpu.models import decoder

    kept = Kept()
    inner = getattr(decoder, "_index_keep", None)
    if inner is None:
        yield kept
        return

    @functools.wraps(inner)
    def tapped(cfg, score, visible):
        keep = inner(cfg, score, visible)
        jax.debug.callback(kept._take, keep, ordered=True)
        return keep

    decoder._index_keep = tapped
    try:
        yield kept
    finally:
        decoder._index_keep = inner


def sets_of(mask: np.ndarray, topk: int) -> np.ndarray:
    """[T, A] bool -> [T, topk] int32: each query's kept positions
    ascending, -1 behind them."""
    T = mask.shape[0]
    out = np.full((T, topk), -1, np.int32)
    for t in range(T):
        at = np.flatnonzero(mask[t])
        if len(at) > topk:
            raise ValueError(f"query {t} keeps {len(at)} positions, more "
                             f"than index_topk = {topk}")
        out[t, :len(at)] = at
    return out


def keep_all(first: int, n: int, topk: int) -> np.ndarray:
    """[n, topk]: the sets of positions first .. first + n - 1 where nothing
    was chosen: every position up to the query's own."""
    at = np.arange(topk, dtype=np.int32)[None, :]
    t = first + np.arange(n, dtype=np.int32)[:, None]
    if first + n > topk:
        raise ValueError(f"position {first + n - 1} has more than "
                         f"index_topk = {topk} before it and chose nothing")
    return np.where(at <= t, at, -1).astype(np.int32)
