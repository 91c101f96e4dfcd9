#!/usr/bin/env python3
"""Find a cell's knee, once, on the chip: serve its traffic at several
fixed rates in one process and print one JSON line per rate.

    python3 bench/sweep.py --workload internlm2-20b.chat \\
        --rates 2,3,4,5,6 --seconds 20 --seed 11

Each row gives ``completion_rps``: the window's output tokens per
second over the schedule's mean output length, the rate of requests the
server completes.  The last line gives two readings of the knee:
``queue_knee_rps``, the highest swept rate at which every request due in
the window left the queue (took a row) by the window's close, and
``capacity_rps``, the highest ``completion_rps``.  A rate above the
capacity grows the queue without bound.  The sweep runs without the
mix's backlog, so each rate starts from an idle server; with
``--backlog`` it keeps it, so every row is full when the window opens
and ``completion_rps`` is the capacity even where requests live longer
than the window.  The cell's ``rate_rps`` is then fixed from the knee
(below it for a tail cell, above it for a throughput cell); the
benchmark's runs never search for a rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--backlog", action="store_true",
                    help="keep the mix's backlog")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from repro.launch.cache import use_compile_cache
    use_compile_cache(ROOT)
    import numpy as np

    from bench import harness, program, spec
    from bench.metrics import itl_p95_ms, output_tok_s, ttft_p90_ms
    from bench.reference import seed_key
    from bench.serve_loop import drive, warm_up

    cell = spec.load_cell(args.workload)
    harness.devices(int(cell.workload.get("chips", 1)), True)
    dims, model, params = program.load(cell, seed_key(args.seed))
    gen = spec.generator(cell.mix["generator"], cell.bench)
    mix = json.loads(json.dumps(cell.mix))
    if not args.backlog:
        mix["arrivals"]["backlog"] = 0
    lead = float(cell.workload["lead_s"])
    knee, capacity = None, 0.0
    for rate in (float(r) for r in args.rates.split(",")):
        engine = program.make_engine(model, params, cell.engine)
        rng = np.random.default_rng([args.seed, 1])
        warm_up(engine, dims.vocab, cell.engine["chunk_size"], rng)
        sched = gen.generate(mix, rate, lead + args.seconds, args.seed)
        prompts = [rng.integers(0, dims.vocab, int(n), dtype=np.int32)
                   for n in sched.prompt_len]
        rec = drive(engine, sched, prompts, lead_s=lead,
                    seconds=args.seconds, grace_s=0.0)
        run = harness.RunData(cell, dims, None, 1, 0.0, rec, None)
        due = rec.due_in_window()
        row = {"rate_rps": rate, "due": len(due),
               "placed_by_close": sum(1 for r in due if r.placed is not None
                                      and r.placed <= rec.t1),
               "first_token_by_close": sum(1 for r in due if r.token_t
                                           and r.token_t[0] <= rec.t1),
               "output_tok_s": output_tok_s.read(run),
               "completion_rps": output_tok_s.read(run)
               / float(np.mean(sched.output_len)),
               "ttft_p90_ms": ttft_p90_ms.read(run),
               "itl_p95_ms": itl_p95_ms.read(run),
               "steps": len(rec.steps), "preempted": rec.preempted}
        print(json.dumps(row), flush=True)
        if row["placed_by_close"] == row["due"]:
            knee = rate
        capacity = max(capacity, row["completion_rps"])
        del engine
    print(json.dumps({"queue_knee_rps": knee, "capacity_rps": capacity}),
          flush=True)
    return 0


if __name__ == "__main__":
    t = time.perf_counter()
    rc = main()
    print(f"sweep took {time.perf_counter() - t:.1f} s", file=sys.stderr)
    sys.exit(rc)
