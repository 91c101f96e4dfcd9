#!/usr/bin/env python3
"""Run one cell of the serving benchmark once.

    python3 bench/run.py --workload internlm2-20b.chat --seed 7 \\
        --seconds 30 --trace 0

Loads the cell's files (``bench/workloads/<cell>.json`` and what it
names), sets up the engine, serves the cell's open-loop traffic for
``--seconds``, checks the served tokens against the plain reference and
prints one JSON object as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics from a profiler trace of
the window), ``device`` and, last, ``checks``: each compared number
beside its limit.  Diagnostics go to standard error, whose last lines
repeat the checks.  Exits 2, printing no result, without a TPU, with
fewer chips than the cell asks for, or without the program's sources.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program sources under {ROOT / 'src'}", file=sys.stderr)
        return 2
    from bench import harness, spec
    cell = spec.load_cell(args.workload)
    from repro.launch.cache import use_compile_cache
    harness.log(f"compile cache: {use_compile_cache(ROOT)}")
    try:
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
