"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the
longest, goes through the float32 reference that the configuration
names (``bench/references/<name>.py``), one whole sequence at a time:
its prompt followed by the tokens the engine served.  At each served
position the reference's best logit minus its logit of the served token
is a gap; greedy decoding at the stated precision keeps it near 0.  Compared numbers:

* ``max_logit_gap``: the widest gap over the sample, against the cell's
  limit (set from sound runs and the fp8 control, see ``PERF.md``);
* ``bad_requests``: requests that got more tokens than asked, a token
  outside the vocabulary, or were retired short, plus corrupted steps the
  engine dropped; limit 0.
"""
from __future__ import annotations

import math
import sys

import jax
import numpy as np

from bench.reference import served_gaps

BLOCK = 512                 # reference query rows per attention block


def sample(records, seed: int, want_tokens: int, max_requests: int):
    """Finished requests: the longest, then others in an order drawn
    from the seed, until ``want_tokens`` served tokens or
    ``max_requests`` requests."""
    done = [r for r in records.requests if r.finished]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + r.out_len, r.index))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed), 7]).permutation(len(rest))
    out = [longest]
    for i in order:
        if len(out) >= max_requests or \
                sum(r.out_len for r in out) >= want_tokens:
            break
        out.append(rest[int(i)])
    return out


def is_bad(r, vocab: int) -> bool:
    """A request retired short, given more tokens than asked, or given a
    token outside the vocabulary."""
    toks = list(r.req.tokens)
    short = r.req.finished_s > 0 and len(toks) != r.out_len
    return short or len(toks) > r.out_len or \
        any(t < 0 or t >= vocab for t in toks)


def bad_requests(records, vocab: int, integrity_failures: int) -> int:
    return integrity_failures + sum(is_bad(r, vocab)
                                    for r in records.requests)


def seq_len(max_len: int) -> int:
    """The one sequence length the reference runs at (a multiple of
    ``BLOCK``), so it compiles once per cell."""
    return int(math.ceil(max_len / BLOCK) * BLOCK)


def gaps(picked, key, ref, dims, max_len: int, max_out: int,
         control: bool = False):
    """Widest gap over ``picked`` (and the fp8 control's, when asked)
    against the reference module ``ref``."""
    if not picked:
        return None, None
    w = jax.jit(ref.weights, static_argnums=1)(key, dims)
    T = seq_len(max_len)
    worst, worst_c = 0.0, 0.0
    every, every_c = [], []
    for r in picked:
        served = np.asarray(r.req.tokens[:r.out_len], np.int32)
        P, n = len(r.prompt), len(served)
        toks = np.zeros(T, np.int32)
        toks[:P] = r.prompt
        toks[P:P + n - 1] = served[:-1]
        at = np.minimum(P - 1 + np.arange(max_out), T - 1).astype(np.int32)
        nxt = np.zeros(max_out, np.int32)
        nxt[:n] = served
        g, gc = served_gaps(w, toks, at, nxt, hidden=ref.hidden, dims=dims,
                            block=BLOCK, control=control)
        every.extend(np.asarray(g)[:n].tolist())
        every_c.extend(np.asarray(gc)[:n].tolist())
        worst = max(worst, max(every[-n:]))
        worst_c = max(worst_c, max(every_c[-n:]))
    del w
    print(f"gaps over {len(every)} served tokens: mean {np.mean(every)}, "
          f"share above 1e-3 {np.mean(np.asarray(every) > 1e-3)}"
          + (f"; control mean {np.mean(every_c)}, share above 1e-3 "
             f"{np.mean(np.asarray(every_c) > 1e-3)}" if control else ""),
          file=sys.stderr, flush=True)
    return worst, (worst_c if control else None)
