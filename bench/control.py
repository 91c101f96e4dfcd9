#!/usr/bin/env python3
"""Read, on the chip, the two readings a cell's ``max_logit_gap`` limit
is set from: the program's widest gap on many seeds, and the fp8
control's on some of them (``bench/reference.py``: every matmul of the
reference computed from float8 e4m3 inputs, at each position of the same
prompts and served tokens).  One process, one JSON line per seed.

    python3 bench/control.py --workload internlm2-20b.chat \\
        --seeds 21,22,23 --control 21,22,23 --seconds 10

Each seed is a whole run of the cell at its own load and sizes
(``harness.run_cell``).  On a control seed the control takes the
program's place in the harness's own comparison: ``correct`` is the
verdict on the control and has to come out false; the program's gap on
the same served tokens is printed beside it.  The benchmark's own runs
never run the control.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from repro.launch.cache import use_compile_cache
    use_compile_cache(ROOT)
    from bench import harness, spec

    cell = spec.load_cell(args.workload)
    ctrl = {int(s) for s in args.control.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, seed, args.seconds, False,
                               t_start=time.perf_counter(),
                               control=seed in ctrl)
        row = {"seed": seed, "control": seed in ctrl,
               "correct": out["correct"], "attempted": out["attempted"],
               "metrics": out["metrics"], "checks": out["checks"]}
        if seed in ctrl:
            row["program_max_logit_gap"] = out["program_max_logit_gap"]
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
