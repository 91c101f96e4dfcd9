"""Open-loop arrivals with heavy-tailed prompt and output lengths.

A mix file gives::

    {"generator": "open_loop",
     "arrivals": {"process": "poisson", "block": 8, "backlog": 48},  # or
                 {"process": "gamma", "cv": 2.0, "block": 8},
     "prompt_tokens": {"dist": "lognormal", "median": 512, "sigma": 1.0,
                       "min": 32, "max": 4096},
     "output_tokens": {...same keys...},
     "block": 48, "strata": 8}

and the cell gives the mean rate.  The schedule is drawn so that every
seed gets the same work in another order.  The gaps come in
consecutive blocks of ``arrivals.block`` and the lengths in blocks of
``block`` (default: all requests in one block): in each block the
values are the distribution's quantiles at ``(i + 1/2) / block``
(lengths clipped), and the seed only permutes each block.  A request's
prompt and output lengths are paired by one shuffle fixed for every
seed, so a block of lengths is one set of requests.  So the mean rate is
exact, and any stretch of the schedule a few blocks long, such as the
measured window, holds the same lengths and gaps whatever the seed: two
seeds differ in which requests meet in the queue, not in how much there
is to do.  Requests are independent users: arrivals do not wait for
earlier answers.  ``arrivals.backlog`` (default 0) requests fall due
together at time 0, ahead of the stream: a server that is already
behind, so that a cell above the knee opens its window on full rows.
With ``block`` equal to the backlog, the backlog holds the same requests
whatever the seed.  ``strata`` (default 1) stratifies each block's
order by prompt length, which sets a request's prefill chunks and the
pages every decode step reads for it: every run of ``strata``
consecutive requests holds one prompt of each band of lengths, so a
stretch of a few such runs, such as the requests a window takes from
the queue, carries nearly the same work for every seed.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


PAIRING_SEED = 0            # not the run's seed: see ``generate``


@dataclasses.dataclass(frozen=True)
class Schedule:
    due_s: np.ndarray           # [n] seconds after the schedule starts
    prompt_len: np.ndarray      # [n] int
    output_len: np.ndarray      # [n] int

    def __len__(self) -> int:
        return len(self.due_s)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: dict, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.asarray([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(spec: dict, n: int, rate: float) -> np.ndarray:
    """``n`` inter-arrival gaps with mean exactly ``1 / rate``."""
    q = _quantiles(n)
    if spec["process"] == "poisson":
        g = -np.log1p(-q)                               # Exp(1) quantiles
    elif spec["process"] == "gamma":
        from scipy.stats import gamma
        shape = 1.0 / spec["cv"] ** 2                   # CV = 1/sqrt(k)
        g = gamma.ppf(q, shape)
    else:
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    return g / g.mean() / rate


def _blocks(values: np.ndarray, n: int, rng, strata: int = 1) -> np.ndarray:
    """``n`` values (rows): ``values`` (one block) repeated, each copy
    permuted.  With ``strata`` > 1 the permutation is stratified: the
    block, in the order given, is cut into ``strata`` equal bands, and
    each run of ``strata`` consecutive values of a copy holds one value of
    each band, in an order drawn from ``rng``."""
    k = len(values)
    if k % strata:
        raise ValueError(f"a block of {k} does not split into {strata} "
                         "strata")
    bands = np.arange(k).reshape(strata, k // strata)

    def one():
        rounds = np.stack([rng.permutation(b) for b in bands], 1)
        return values[np.concatenate([rng.permutation(r) for r in rounds])]
    reps = -(-n // k)
    return np.concatenate([one() for _ in range(reps)])[:n]


def generate(mix: dict, rate: float, duration_s: float,
             seed: int) -> Schedule:
    """The schedule of requests due in ``[0, duration_s)`` at mean rate
    ``rate`` requests per second."""
    if rate <= 0 or duration_s <= 0:
        raise ValueError("rate and duration must be positive")
    n = max(int(math.ceil(rate * duration_s)), 1)
    rng = np.random.default_rng(seed)
    arrivals = mix["arrivals"]
    gaps = _blocks(_gaps(arrivals, min(arrivals.get("block", n), n), rate),
                   n, rng)
    due = np.cumsum(gaps) - gaps[0]                     # first due at 0
    due = np.concatenate([np.zeros(int(arrivals.get("backlog", 0))), due])
    keep = due < duration_s
    n = len(due)
    block = min(mix.get("block", n), n)
    # one fixed pairing of prompt and output quantiles, the same for every
    # seed: a block is then one set of requests, not only of lengths
    pairing = np.random.default_rng(PAIRING_SEED).permutation(block)
    lengths = np.stack([_lengths(mix["prompt_tokens"], block),
                        _lengths(mix["output_tokens"], block)[pairing]], 1)
    prompt, output = _blocks(lengths, n, rng, int(mix.get("strata", 1))).T
    return Schedule(due_s=due[keep], prompt_len=prompt[keep],
                    output_len=output[keep])
