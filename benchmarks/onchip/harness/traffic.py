"""Traffic generators: one general reader of the mixes in ``traffic/``.

A mix file names a generator ``kind`` and its parameters.  Every size
a run uses is a stratified draw: the quantiles of the mix's
distribution at (i + 0.5) / n, put in an order that the seed picks.
Two seeds therefore offer the same work (the same multiset of prompt
and output lengths) in a different order, with different token ids, so
the spread between seeds is the system's and not the generator's.

Kinds:
  closed_loop   ``clients_per_slot`` callers for every slot of the
                configuration, each sending its next request when its
                last one finishes.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

RID_PROBE = 1 << 28         # set-up probes that warm the programs


@dataclass
class Item:
    """One request as the client sends it."""
    rid: int
    prompt: np.ndarray
    max_new: int
    temperature: float
    top_k: int
    top_p: float
    warm: bool = False
    client: int = -1

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def quantile(dist: dict, u: float) -> float:
    """The ``u`` quantile (0 < u < 1) of a size distribution."""
    kind = dist["dist"]
    if kind == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        return math.exp(lo + u * (hi - lo))
    if kind == "uniform":
        return dist["min"] + u * (dist["max"] - dist["min"])
    raise KeyError(f"unknown distribution {kind!r} (loguniform, uniform)")


def stratified(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n quantiles of ``dist`` at (i + 0.5) / n, rounded, in an order from
    ``rng``."""
    vals = np.asarray([int(round(quantile(dist, (i + 0.5) / n)))
                       for i in range(n)])
    return vals[rng.permutation(n)]


def _greedy_flags(n: int, share: float, rng) -> np.ndarray:
    k = int(round(n * share))
    flags = np.zeros(n, bool)
    flags[:k] = True
    return flags[rng.permutation(n)]


def _items(mix: dict, n: int, rng, vocab: int, rid0: int, warm: bool,
           output: Optional[dict] = None) -> List[Item]:
    samp = mix["sampling"]
    plens = stratified(mix["prompt"], n, rng)
    olens = stratified(output or mix["output"], n, rng)
    greedy = _greedy_flags(n, samp.get("greedy_share", 0.0), rng)
    items = []
    for i in range(n):
        g = bool(greedy[i]) or samp["temperature"] <= 0.0
        items.append(Item(
            rid=rid0 + i,
            prompt=rng.integers(1, vocab, size=int(plens[i]), dtype=np.int32),
            max_new=int(olens[i]),
            temperature=0.0 if g else float(samp["temperature"]),
            top_k=0 if g else int(samp.get("top_k", 0)),
            top_p=1.0 if g else float(samp.get("top_p", 1.0)),
            warm=warm))
    return items


class ClosedLoop:
    """Closed loop: ``clients_per_slot`` x ``slots`` callers, each sending
    its next request the moment its last one finishes.  Request contents come in blocks of
    ``clients`` stratified sizes, block by block in the order callers ask.
    The first wave (one request per client) may draw its outputs from
    ``first_wave_output`` so that callers finish at staggered times from
    the start, as they would in steady state."""

    def __init__(self, mix: dict, seed: int, vocab: int, slots: int):
        self.mix = mix
        self.vocab = vocab
        self.clients = int(mix["clients_per_slot"]) * slots
        self.warm_s = float(mix.get("warm_s", 0.0))
        self._rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
        self._pool: List[Item] = []
        self._issued = 0
        self._ready: List[Tuple[float, int]] = [
            (-self.warm_s, c) for c in range(self.clients)]
        self._stopped = False
        first = _items(mix, self.clients, self._rng, vocab, 0, True,
                       output=mix.get("first_wave_output"))
        self._pool.extend(first)

    @property
    def start(self) -> float:
        return -self.warm_s

    def _take(self, warm: bool) -> Item:
        if not self._pool:
            self._pool = _items(self.mix, self.clients, self._rng,
                                self.vocab, self._issued, warm)
        item = self._pool.pop(0)
        item.rid = self._issued
        item.warm = warm
        self._issued += 1
        return item

    def due(self, now: float) -> List[Tuple[float, Item]]:
        if self._stopped:
            return []
        out = []
        keep = []
        for t, c in self._ready:
            if t <= now:
                item = self._take(warm=t < 0)
                item.client = c
                out.append((t, item))
            else:
                keep.append((t, c))
        self._ready = keep
        return out

    def next_time(self) -> Optional[float]:
        if self._stopped or not self._ready:
            return None
        return min(t for t, _ in self._ready)

    def finished(self, item: Item, now: float):
        if item.client >= 0 and not self._stopped:
            self._ready.append((now, item.client))

    def stop(self):
        self._stopped = True


KINDS: Dict[str, type] = {"closed_loop": ClosedLoop}


def make(mix: dict, seed: int, vocab: int, slots: int):
    try:
        kind = KINDS[mix["kind"]]
    except KeyError:
        raise KeyError(f"traffic {mix.get('name')!r}: unknown kind "
                       f"{mix.get('kind')!r}; have {sorted(KINDS)}") from None
    return kind(mix, seed, vocab, slots)
