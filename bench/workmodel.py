#!/usr/bin/env python3
"""How much work a cell's schedule gives each seed: a host-side model of
the paged engine, no JAX and no chip.

    python3 bench/workmodel.py --workload internlm2-20b.chat \\
        --seeds 8301,8302,8303 --seconds 51

For each seed it prints the output tokens per second that the engine
would deliver in the window if its costs were exactly those measured on
one TPU v5e for internlm2-20b at 3 layers (PERF.md, sections 5 and 6):
``CHUNK_MS`` a prefill chunk call; a decode step ``STEP_MS`` plus
``PAGE_US`` per row, query head, KV page and layer.  The engine is
modelled as it plans without a cost model: rows fill from the queue in
order, every prefilling row runs one chunk a step (its last chunk gives
the first token, which counts as output), then every prefilled row
decodes one token a step until it has its output.  The model does not
predict the chip's numbers: it ranks seeds by the work their schedule
holds, so a mix can be made steady across seeds before chip time is
spent on it.
"""
from __future__ import annotations

import argparse
import math
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CHUNK_MS, STEP_MS, PAGE_US = 42.7, 25.0, 0.38


def tokens_per_s(sched, *, rows: int, chunk: int, block: int, heads: int,
                 layers: int, lead_s: float, seconds: float,
                 chunk_ms: float, step_ms: float, page_us: float) -> float:
    """Output tokens emitted in ``[lead_s, lead_s + seconds)`` of the
    schedule ``sched``, per second."""
    page_s = heads * layers * page_us * 1e-6
    t, nxt, emitted = 0.0, 0, 0
    queue = []
    slots = [None] * rows       # [request, chunks left, tokens out]
    while t < lead_s + seconds:
        while nxt < len(sched) and sched.due_s[nxt] <= t:
            queue.append(nxt)
            nxt += 1
        for i, s in enumerate(slots):
            if s is None and queue:
                j = queue.pop(0)
                slots[i] = [j, math.ceil(int(sched.prompt_len[j]) / chunk), 0]
        chunks, firsts, pages, ready = 0, 0, 0, []
        for s in slots:
            if s is None:
                continue
            if s[1]:
                s[1] -= 1
                chunks += 1
                s[2] = int(s[1] == 0)
                firsts += s[2]
            else:
                ready.append(s)
                pages += math.ceil((int(sched.prompt_len[s[0]]) + s[2])
                                   / block)
        dt = chunks * chunk_ms * 1e-3
        if ready:
            dt += step_ms * 1e-3 + pages * page_s
        t += max(dt, 1e-3)
        for s in ready:
            s[2] += 1
        if lead_s <= t < lead_s + seconds:
            emitted += firsts + len(ready)
        for i, s in enumerate(slots):
            if s is not None and not s[1] \
                    and s[2] >= int(sched.output_len[s[0]]):
                slots[i] = None
    return emitted / seconds


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT)]
    from bench import spec
    cell = spec.load_cell(args.workload)
    wl, eng, model = cell.workload, cell.engine, cell.config["model"]
    gen = spec.generator(cell.mix["generator"])
    lead, grace = float(wl["lead_s"]), float(wl["grace_s"])
    reads = []
    for seed in (int(s) for s in args.seeds.split(",")):
        sched = gen.generate(cell.mix, float(wl["rate_rps"]),
                             lead + args.seconds + grace, seed)
        reads.append(tokens_per_s(
            sched, rows=eng["max_batch"], chunk=eng["chunk_size"],
            block=eng["block_size"], heads=model["num_attention_heads"],
            layers=model["num_hidden_layers"], lead_s=lead,
            seconds=args.seconds, chunk_ms=CHUNK_MS, step_ms=STEP_MS,
            page_us=PAGE_US))
        print(f"{seed} {reads[-1]:.3f}")
    if len(reads) >= 2:
        q1, med, q3 = statistics.quantiles(reads, n=4)
        print(f"median {med:.3f}, quartile spread {(q3 - q1) / med:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
