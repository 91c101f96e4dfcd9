#!/usr/bin/env python3
"""Run one cell traced, as ``bench/run.py --trace 1`` does, and read the
program's own spans and scopes from the trace as well.

    python3 bench/trace_program.py --workload internlm2-20b.chat \\
        --seed 7 --seconds 51

``bench/trace.py`` keeps only the benchmark's spans, so ``BENCHMARK.json``
cannot list the metrics that read the program's (``program_metrics``).
Here the summary of the trace carries them (``program_trace.load``) and
the cell is given those metrics besides its own.  The last line is
the run's result line, its ``breakdown.idle_gaps`` each named after the
innermost span over the gap, the program's included, and with
``program_checks``: the scoped operations' time against the kernel's by
name, op by op, and host work plus the mean waits per step against
``engine_step_ms``.  Exits 2 without a TPU, as ``bench/run.py`` does.
A ``benchmark`` PR that folds ``program_trace`` into ``bench/trace.py``
deletes this file.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def program_metrics(cell: str):
    """The per-layer entries the program's spans and scopes give in
    ``cell``, each moving the cell's ``output_tok_s``."""
    kind = cell.rsplit(".", 1)[1]
    return [{"name": f"{base}.{kind}", "unit": unit, "better": better,
             "source": source, "layer": layer,
             "moves": f"output_tok_s.{kind}", "workloads": [cell]}
            for base, unit, better, source, layer in (
                ("host_step_ms", "ms", "lower", "program_span",
                 "serve.engine"),
                ("decode_rows", "rows", "higher", "program_span",
                 "serve.engine"),
                ("paged_attention_ms", "ms", "lower", "device_trace",
                 "kernels"))]


def checks(summary) -> dict:
    """What the acceptance of the new metrics compares on one trace."""
    from bench.metrics.paged_attention_ms import SCOPE
    from bench.metrics.paged_attention_roofline import KERNEL
    from bench.serve_loop import STEP
    pt = summary.program
    ops = summary.ops[0] if summary.ops else []
    by_op = defaultdict(float)
    for e in ops:
        if pt.in_scope(e.name, SCOPE):
            by_op[e.name.split(" = ", 1)[0]] += e.dur
    split = pt.split()
    n_bench = sum(1 for e in summary.host if e.name == STEP
                  and summary.window[0] <= e.start <= summary.window[1])
    kernel_op = next((e.name for e in ops if e.name.startswith(KERNEL[0])),
                     None)
    # ms a step, on the mean, of each span in the window, and of host work
    per_step = defaultdict(float)
    for s in pt.spans:
        if summary.window[0] <= s.start <= summary.window[1]:
            per_step[s.name] += s.dur
    for r in split:
        per_step["host"] += r["host"]
    return {
        "scoped_s": pt.scoped_s(ops, SCOPE),
        "kernel_by_name_s": summary.op_time(KERNEL)[1],
        "scoped_by_op_s": dict(sorted(by_op.items(), key=lambda x: -x[1])),
        "kernel_scope": pt.scopes.get(kernel_op) if kernel_op else None,
        "serve_steps": len(split), "bench_steps": n_bench,
        "step_ms": {k: 1e3 * v / max(len(split), 1)
                    for k, v in sorted(per_step.items())},
        "engine_step_ms": 1e3 * summary.window_s / max(n_bench, 1),
    }


def run(cell, seed: int, seconds: float, **kw) -> dict:
    """``harness.run_cell`` traced, with the program's part of the trace
    attached to its summary; the result line gains ``program_checks``."""
    from bench import harness, program_trace, trace
    seen = {}
    load = trace.load

    def with_program(log_dir):
        seen["summary"] = program_trace.load(log_dir)
        return seen["summary"]

    trace.load = with_program
    try:
        out = harness.run_cell(cell, seed, seconds, True, **kw)
    finally:
        trace.load = load
    out["program_checks"] = checks(seen["summary"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, spec
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["per_layer"] += program_metrics(args.workload)
    cell = spec.load_cell(args.workload, bench)
    from repro.launch.cache import use_compile_cache
    harness.log(f"compile cache: {use_compile_cache(ROOT)}")
    try:
        out = run(cell, args.seed, args.seconds, t_start=T_START)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
