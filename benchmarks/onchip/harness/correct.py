"""How a run decides ``correct``: the served tokens against the reference.

A sample of the greedy requests the run finished, drawn from the seed
with the longest among them, is run through the reference once: each
prompt followed by the tokens the program served.  For every served
token the number read is how far its reference logit lies below the
reference's best logit at that position (0 where the program picked the
reference's own argmax).  From these gaps a configuration compares, each
against its own limit, whichever it names of:

  max_logit_gap   the widest gap of the sample;
  mean_logit_gap  their mean;
  far_tokens      how many served tokens lie more than ``far_gap`` below
                  the reference's best (one wrong token is enough).

Served requests must also carry exactly the tokens they asked for.

The control takes the program's place in the same comparison: at every
served position, the token that the reference computed with float8
weight matmuls puts first is scored as if the program had served it.
"""
from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import numpy as np

from harness import reference


def choose(finished: Sequence[Tuple[object, List[int]]], n: int,
           rng: np.random.Generator):
    """Up to ``n`` of the finished greedy requests: the one that served
    the most tokens, then others drawn by ``rng``."""
    greedy = [f for f in finished if f[0].greedy]
    if not greedy:
        return []
    greedy.sort(key=lambda f: f[0].rid)
    longest = max(range(len(greedy)),
                  key=lambda i: (len(greedy[i][1]), -greedy[i][0].rid))
    rest = [i for i in range(len(greedy)) if i != longest]
    picks = [longest] + list(rng.permutation(rest)[:n - 1])
    return [greedy[i] for i in picks]


def batch(sample, rows: int, length: int):
    """Fixed-shape (rows, length) token batch: prompt then served tokens
    except the last (the model never sees its own last output), and the
    next-token ids to look up at every position."""
    tokens = np.zeros((rows, length), np.int32)
    nxt = np.zeros((rows, length), np.int32)
    spans = []
    for r, (item, out) in enumerate(sample):
        seq = np.concatenate([np.asarray(item.prompt, np.int32),
                              np.asarray(out, np.int32)])
        n = len(seq) - 1
        if n > length:
            raise ValueError(f"request {item.rid}: {n} positions exceed "
                             f"the reference batch length {length}")
        tokens[r, :n] = seq[:n]
        nxt[r, :n] = seq[1:]
        spans.append((r, len(item.prompt) - 1, n))   # served positions
    return tokens, nxt, spans


def gaps(layout: dict, seed: int, sample, rows: int, length: int,
         far_gap: float = math.inf, control: bool = False) -> dict:
    """Readings of the served tokens under ``"program"`` and, with
    ``control``, of the tokens the float8 control would put first under
    ``"control"``; each holds ``gap`` (widest), ``mean_gap``,
    ``far_tokens`` (gaps above ``far_gap``), ``disagree`` (share off the
    reference's argmax), ``tokens`` and the widest gap ``per_request``."""
    tokens, nxt, spans = batch(sample, rows, length)
    lookup = nxt[..., None]
    if control:
        _, c_argmax, _ = reference.run(layout, seed, tokens, lookup,
                                       mode="fp8")
        lookup = np.stack([nxt, c_argmax], -1)
    mx, _, at = reference.run(layout, seed, tokens, lookup, mode="f32")
    finite = bool(np.all(np.isfinite(mx)))
    out = {}
    for j, who in enumerate(["program", "control"][:lookup.shape[-1]]):
        per = [mx[r, a:b] - at[r, a:b, j] for r, a, b in spans]
        out[who] = _read(np.concatenate(per), far_gap, finite)
        out[who]["per_request"] = [float(np.max(g)) for g in per]
    return out


def _read(gaps: np.ndarray, far_gap: float, finite: bool) -> dict:
    if not finite:
        return {"gap": math.inf, "mean_gap": math.inf,
                "far_tokens": int(gaps.size), "disagree": 1.0,
                "tokens": int(gaps.size)}
    return {"gap": float(np.max(gaps)), "mean_gap": float(np.mean(gaps)),
            "far_tokens": int(np.sum(gaps > far_gap)),
            "disagree": float(np.mean(gaps > 0)), "tokens": int(gaps.size)}
