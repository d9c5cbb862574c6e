"""The one traffic generator: requests drawn from a mix's parameters
(``bench/traffic/<name>.json``) and the seed.

The requests themselves are fixed by the mix and the run's length: how
many there are, each one's popularity rank, lengths and holding time
are stratified draws (the quantiles ``(k + 1/2)/n`` of their
distributions), paired up without the seed.  The seed only deals them
out: every seed sends the same set of requests at other moments, and
the fleet gives each rank to another tenant.

When they fall due is the mix's ``arrivals`` process:

* ``poisson`` — an open loop at ``rate_rps``: ``rate_rps`` times the
  horizon requests at sorted uniform times (a Poisson process given its
  count);
* ``on_off`` — an open loop of bursts at the same mean ``rate_rps``:
  on-periods of ``on_s`` seconds alternate with ``off_s`` seconds of
  silence, from a phase drawn from the seed, and the requests fall at
  sorted uniform times within the on-periods;
* ``closed`` — ``clients`` clients, each with one request outstanding:
  the first requests are due at once, and when a call decides a
  client's request its next one falls due ``think_s`` later.  At most
  ``max_rps`` times the horizon requests are drawn; a run that uses
  them all offers nothing more, and says so.

Due times of an open loop are known before the run; those of a closed
loop are set by :meth:`Arrivals.decided` as the loop runs.  In both,
due times never fall as the request index grows, so the requests due
by any moment are a prefix of those not yet dispatched.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

from bench.harness.fleet import zipf_pmf


@dataclasses.dataclass
class Arrivals:
    """One run's requests, in due order (``inf``: not yet issued)."""

    due: np.ndarray          # f64 seconds since the loop started
    key: list                # API key
    input_tokens: np.ndarray
    max_tokens: np.ndarray
    output_tokens: np.ndarray   # generated before the request completes
    hold_s: np.ndarray       # decision -> completion, when admitted
    think_s: float | None = None    # closed loop: decision -> next due
    issued: int = 0          # closed loop: requests given a due time

    def __len__(self) -> int:
        return len(self.due)

    def decided(self, lo: int, hi: int, t: float) -> None:
        """Requests ``[lo, hi)`` were decided at ``t``: in a closed loop
        each of their clients issues its next request."""
        if self.think_s is None:
            return
        new = min(hi - lo, len(self.due) - self.issued)
        self.due[self.issued:self.issued + new] = t + self.think_s
        self.issued += new

    @property
    def exhausted(self) -> bool:
        """A closed loop has issued every request it drew."""
        return self.think_s is not None and self.issued >= len(self.due)


def _stratified(rng, n: int) -> np.ndarray:
    return rng.permutation((np.arange(n) + 0.5) / n)


def _lognormal(u: np.ndarray, median: float, sigma: float,
               lo: float, hi: float) -> np.ndarray:
    inv = NormalDist().inv_cdf
    z = np.fromiter((inv(x) for x in u), np.float64, count=len(u))
    return np.clip(np.exp(np.log(median) + sigma * z), lo, hi)


def count(arrivals: dict, horizon: float) -> int:
    """How many requests a run of ``horizon`` seconds draws."""
    process = arrivals["process"]
    if process in ("poisson", "on_off"):
        return int(round(float(arrivals["rate_rps"]) * horizon))
    if process == "closed":
        return int(round(float(arrivals["max_rps"]) * horizon))
    raise ValueError(f"unknown arrival process {process!r}")


def due_times(arrivals: dict, n: int, horizon: float, rng) -> np.ndarray:
    """When each of ``n`` requests falls due, in order."""
    process = arrivals["process"]
    if process == "poisson":
        return np.sort(rng.uniform(0.0, horizon, n))
    if process == "on_off":
        on, off = float(arrivals["on_s"]), float(arrivals["off_s"])
        period = on + off
        phase = rng.uniform(0.0, period)
        # the on-periods that meet [0, horizon), as (start, end) pairs
        starts = np.arange(-phase, horizon, period)
        spans = np.clip(np.stack([starts, starts + on], 1), 0.0, horizon)
        lengths = spans[:, 1] - spans[:, 0]
        # uniform times over the total on-time, mapped back to the clock
        u = np.sort(rng.uniform(0.0, lengths.sum(), n))
        ends = np.cumsum(lengths)
        k = np.minimum(np.searchsorted(ends, u, "right"), len(ends) - 1)
        return spans[k, 0] + u - (ends[k] - lengths[k])
    if process == "closed":
        due = np.full(n, np.inf)
        due[:min(n, int(arrivals["clients"]))] = 0.0
        return due
    raise ValueError(f"unknown arrival process {process!r}")


def generate(mix: dict, keys_by_rank: list, seed: int,
             seconds: float) -> Arrivals:
    """Requests for ``[0, burn_in_s + seconds)``.  ``keys_by_rank``
    holds, per model, the key of each popularity rank; every model draws
    an equal share of the requests.  The requests themselves (model,
    rank, lengths) depend on the mix and the run's length alone; the
    seed deals them out in another order, at other moments."""
    horizon = float(mix["burn_in_s"]) + float(seconds)
    arrivals = mix["arrivals"]
    n = count(arrivals, horizon)
    fixed = np.random.default_rng(0)
    n_keys = len(keys_by_rank[0])
    cdf = np.cumsum(zipf_pmf(n_keys, float(mix["zipf_s"])))
    ranks = np.minimum(np.searchsorted(cdf, _stratified(fixed, n)),
                       n_keys - 1)
    # lengths: each regime takes its share of the requests
    regime = np.zeros(n, np.int64)
    shares = np.array([r["share"] for r in mix["regimes"]], np.float64)
    bounds = np.floor(np.cumsum(shares / shares.sum()) * n).astype(int)
    pos = fixed.permutation(n)
    for k in range(1, len(bounds)):
        regime[pos[bounds[k - 1]:bounds[k]]] = k
    inp = np.zeros(n)
    mx = np.zeros(n)
    for k, r in enumerate(mix["regimes"]):
        idx = np.flatnonzero(regime == k)
        inp[idx] = _lognormal(_stratified(fixed, idx.size),
                              r["input_median"], r["input_sigma"],
                              r["input_min"], r["input_max"])
        mx[idx] = _lognormal(_stratified(fixed, idx.size),
                             r["max_tokens_median"], r["max_tokens_sigma"],
                             r["max_tokens_min"], r["max_tokens_max"])
    inp = np.round(inp).astype(np.int64)
    mx = np.round(mx).astype(np.int64)
    used = float(mix["output_used_min"])
    frac = used + (1.0 - used) * _stratified(fixed, n)
    out = np.maximum(1, np.floor(frac * mx)).astype(np.int64)
    hold = inp / float(mix["prefill_tps"]) + out / float(mix["decode_tps"])
    model = np.zeros(n, np.int64)
    if len(keys_by_rank) > 1:
        model = fixed.permutation(n) * len(keys_by_rank) // n
    rng = np.random.default_rng((seed % (1 << 63)) + 1)
    order = rng.permutation(n)
    due = due_times(arrivals, n, horizon, rng)
    closed = arrivals["process"] == "closed"
    return Arrivals(due=due, key=[keys_by_rank[m][r] for m, r
                                  in zip(model[order], ranks[order])],
                    input_tokens=inp[order], max_tokens=mx[order],
                    output_tokens=out[order], hold_s=hold[order],
                    think_s=float(arrivals["think_s"]) if closed else None,
                    issued=int(np.isfinite(due).sum()))
