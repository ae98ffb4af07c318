"""The one traffic generator: a mix is a data file of parameters.

Every seed gets the SAME multiset of gaps between arrivals and the SAME table
of requests (prompt length, answer length, shares a system prompt or not): the
``n`` mid-quantiles of the named distributions, paired once by ``TABLE_SEED``.
The run's ``--seed`` draws every token (and, in the drivers, every weight and
the sequences ``correct`` checks).  What ORDERS the gaps and the table's rows
is the mix's: its ``order_seed`` (a whole number) where it has one, so that
every run of the cell replays ONE schedule and seeds differ in tokens, weights
and routing alone; the run's ``--seed`` where it has none, so that the work of
a window does not depend on the seed, but where its bursts fall and which
request meets which does.  A mix fixes its order where requests stay seconds
in a window of tens of seconds: how many answers the window's end cuts, and
where the long prompts fall, is then the order's and would be read as the
program's spread.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def quantile_set(dist, n):
    """``n`` mid-quantiles of ``dist`` as floats (sorted ascending)."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "constant":
        x = np.full(n, float(dist["value"]))
    elif kind == "exponential":
        x = -np.log1p(-u) * float(dist["mean"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    else:
        raise ValueError("unknown distribution %r" % (kind,))
    if "min" in dist or "max" in dist:
        x = np.clip(x, dist.get("min", -np.inf), dist.get("max", np.inf))
    return x


# pairs prompt lengths with answer lengths and system prompts, once for all
# seeds and mixes (another pairing would be another amount of work a window)
TABLE_SEED = 20260928


def _rng(seed, salt):
    return np.random.RandomState((seed + salt) % (2 ** 32))


def arrivals(mix, seconds, seed, rate=None):
    """Due times (s from the window's start) of an open loop at
    ``rate_rps`` over ``seconds``: ``round(rate x seconds)`` arrivals whose
    gaps are the exponential ("poisson") or constant ("uniform") quantile
    set, scaled so that the last one falls half a mean gap before the end;
    in the order of the mix's ``order_seed``, or of ``seed`` where it has none."""
    rate = float(mix["rate_rps"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    kind = {"poisson": "exponential", "uniform": "constant"}[mix["arrivals"]]
    gaps = quantile_set({"dist": kind, "mean": 1.0, "value": 1.0}, n)
    gaps = gaps[_rng(mix.get("order_seed", seed), 1).permutation(n)]
    due = np.cumsum(gaps)
    return due * ((seconds - 0.5 / rate) / due[-1])


def requests(mix, n, seed, vocab):
    """``n`` requests: ``(prompt int32[len], new_tokens)``.  ``prompt_len``
    is the user's part; a ``shared_prefix.share`` of the requests open with
    one of ``count`` fixed system prompts of ``tokens`` tokens, prepended
    (the total clipped to ``max_prompt``).  The table of (prompt length,
    answer length, shares or not, which system prompt) is fixed by the mix;
    its ``order_seed``, or ``seed`` where it has none, orders the rows, and
    ``seed`` draws every token."""
    sp = mix.get("shared_prefix") or {"share": 0.0, "count": 1, "tokens": 0}
    base = np.random.RandomState(TABLE_SEED)
    p_lens = np.rint(quantile_set(mix["prompt_len"], n))[base.permutation(n)]
    o_lens = np.rint(quantile_set(mix["output_len"], n))[base.permutation(n)]
    shares = (np.arange(n) < int(round(sp["share"] * n)))[base.permutation(n)]
    order = _rng(mix.get("order_seed", seed), 2).permutation(n)
    rng = _rng(seed, 3)
    systems = [rng.randint(1, vocab, size=sp["tokens"]).astype(np.int32)
               for _ in range(sp["count"])]
    out = []
    for row in order:
        body = rng.randint(1, vocab, size=int(p_lens[row])).astype(np.int32)
        if shares[row]:
            body = np.concatenate([systems[row % sp["count"]], body])
        out.append((body[:mix["max_prompt"]], int(o_lens[row])))
    return out
