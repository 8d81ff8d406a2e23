"""Open-loop arrivals: requests are due on a schedule fixed in advance,
whether or not earlier ones have been served, as from independent users.

Every seed gets the same work in another order.  The inter-arrival gaps,
prompt lengths and output lengths are the midpoint quantiles of their
distributions (exponential gaps at the cell's rate, lognormal lengths
clipped to the mix's range), so the multiset of sizes and gaps depends only
on the mix, the rate and the window; the seed orders each of them and draws
the token ids.  The order is balanced in blocks: each run of ``block``
consecutive arrivals holds one value from each of ``block`` strata of the
sorted values, so every seed offers the same load at the scale of a block
and the seed shuffles the work inside it.  Runs with different seeds then
differ by the order of the work, not by its amount or its clumping.

A schedule may start with a pre-roll: requests due in the ``preroll``
seconds before the window opens, drawn the same way, that bring the system
to its steady state before anything is measured.  Their ``due`` is
negative.

Mix parameters (a ``bench/traffic/<name>.json`` file):

* ``prompt`` / ``output``: ``median``, ``sigma`` (of the log), ``min``,
  ``max`` tokens;
* ``burst``: requests that arrive together (1 = one at a time); groups
  arrive at the cell's rate divided by ``burst``;
* ``block``: arrivals per balanced block;
* ``sessions`` (optional): ``{"count": k, "prefix_len": p}``, every prompt
  starts with one of ``k`` shared prefixes of ``p`` tokens.
"""

import math
import statistics
from typing import Dict, List

import numpy as np

_NORMAL = statistics.NormalDist()


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` lognormal midpoint quantiles, clipped, as whole tokens."""
    z = np.array([_NORMAL.inv_cdf(u) for u in _midpoints(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def balanced(values: np.ndarray, block: int, rng) -> np.ndarray:
    """``values`` reordered so that every ``block`` consecutive entries hold
    one value from each of ``block`` strata of the sorted values; the seed
    picks which value of each stratum goes to which block and the order
    inside each block."""
    strata = [rng.permutation(s) for s in
              np.array_split(np.sort(values), block)]
    out = []
    for b in range(len(strata[0])):
        members = [s[b] for s in strata if b < len(s)]
        out.extend(rng.permutation(members))
    return np.asarray(out)


def _part(mix: Dict, n: int, span: float, rng) -> Dict[str, np.ndarray]:
    """``n`` arrivals in ``[0, span)``, the first at 0 and the gaps scaled
    to add up to ``span``: due times, prompt and output lengths."""
    if n == 0:
        return {k: np.zeros(0) for k in ("due", "prompt", "output")}
    burst = int(mix.get("burst", 1))
    block = int(mix["block"])
    groups = math.ceil(n / burst)
    gaps = -np.log1p(-_midpoints(groups))
    gaps = balanced(gaps * (span / gaps.sum()), block, rng)
    due = np.repeat(np.cumsum(gaps) - gaps, burst)[:n]      # first due at 0
    return {"due": due,
            "prompt": balanced(lengths(mix["prompt"], n), block, rng),
            "output": balanced(lengths(mix["output"], n), block, rng)}


def schedule(mix: Dict, *, rate: float, seconds: float, seed: int,
             vocab: int, preroll: float = 0.0) -> List[Dict]:
    """The requests due from ``-preroll`` to the window's close at
    ``seconds``: dicts with ``uid``, ``due`` (seconds from the window's
    start), ``prompt`` (int32 token ids) and ``max_new_tokens``, in order of
    ``due``.  The window's own requests are the same work whatever the
    pre-roll."""
    rng = np.random.default_rng(int(seed) % (1 << 64))
    window = _part(mix, int(round(rate * seconds)), seconds, rng)
    pre = _part(mix, int(round(rate * preroll)), preroll, rng)
    pre["due"] = pre["due"] - preroll
    parts = {k: np.concatenate([pre[k], window[k]]) for k in window}
    sessions = mix.get("sessions")
    prefixes = None
    if sessions:
        prefixes = rng.integers(0, vocab, (sessions["count"],
                                           sessions["prefix_len"]))
    out = []
    for i in range(len(parts["due"])):
        tokens = rng.integers(0, vocab, int(parts["prompt"][i]))
        if prefixes is not None:
            p = prefixes[rng.integers(0, len(prefixes))]
            k = min(len(p), len(tokens) - 1)
            tokens[:k] = p[:k]
        out.append({"uid": i, "due": float(parts["due"][i]),
                    "prompt": tokens.astype(np.int32),
                    "max_new_tokens": int(parts["output"][i])})
    return out
