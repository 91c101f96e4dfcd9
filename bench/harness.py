"""One run of one cell: set up, measure, check, and build the result line.

``run_cell`` is what ``bench/run.py`` calls, and what the control and
fault tests under ``tests/bench`` call with ``require_tpu=False`` at a
tiny size.  Set-up (``setup_s``) runs from the process start until the
window can open: JAX, the weights (one jitted call, on the device, from
the seed), the engine, and a warm-up that fills every row once so each
program the window runs is compiled or loaded from the persistent cache.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from bench import check, spec
from bench.reference import seed_key
from bench.serve_loop import Records, drive, warm_up
from bench.trace import TraceSummary


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class RunData:
    """What the metric readers read."""
    cell: spec.Cell
    dims: object                # the ``Dims`` of the cell's reference
    peaks: Optional[dict]
    chips: int
    setup_s: float
    records: Records
    trace: Optional[TraceSummary]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def devices(chips: int, require_tpu: bool):
    """The devices this run may use; refuses anything but a TPU with at
    least ``chips`` chips when ``require_tpu``."""
    import jax
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    log(f"device: platform {platform}, device_kind {kind!r}, "
        f"count {len(devs)}")
    if require_tpu and platform != "tpu":
        raise NoChip(f"JAX found {platform!r}, not a TPU; no metric is "
                     "taken from another platform")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


class CompileCount:
    """Counts backend compiles (persistent-cache loads included) from
    JAX's monitoring events, so a compile inside the window shows."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, control: bool = False,
             fault: Optional[Callable] = None) -> dict:
    """Run ``cell`` once and return the result line's object.  ``fault``,
    for tests only, breaks the timed path: it is called with the model
    and returns the model the engine serves.  With ``control`` the fp8
    control (``bench/reference.py``) takes the program's place in the
    comparison that decides ``correct``, on the same prompts and served
    tokens; the program's own gap is returned beside the checks.  The
    reference is the module the cell's configuration names."""
    import jax

    from bench import program

    wl, eng_cfg = cell.workload, cell.engine
    chips = int(wl.get("chips", 1))
    devs = devices(chips, require_tpu)
    peaks = spec.load_peaks(devs[0].device_kind) if require_tpu else None
    compiles = CompileCount()

    key = seed_key(seed)
    dims, model, params = program.load(cell, key)
    served_model = fault(model) if fault is not None else model
    engine = program.make_engine(served_model, params, eng_cfg)
    rng = np.random.default_rng([int(seed), 1])
    warm_up(engine, dims.vocab, eng_cfg["chunk_size"], rng)

    gen = spec.generator(cell.mix["generator"], cell.bench)
    lead, grace = float(wl["lead_s"]), float(wl["grace_s"])
    sched = gen.generate(cell.mix, float(wl["rate_rps"]),
                         lead + seconds + grace, seed)
    prompts = [rng.integers(0, dims.vocab, int(n), dtype=np.int32)
               for n in sched.prompt_len]
    jax.block_until_ready(engine.cache)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; {len(sched)} requests scheduled over "
        f"{lead + seconds + grace:.0f} s at {wl['rate_rps']} req/s")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    n_compiles = compiles.n
    records = drive(engine, sched, prompts, lead_s=lead, seconds=seconds,
                    grace_s=grace)
    in_window = compiles.n - n_compiles
    if trace:
        jax.block_until_ready(engine.cache)
        jax.profiler.stop_trace()
    stats = engine.stats
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    late = np.asarray(records.lateness)
    log(f"generator lateness: median {1e3 * np.median(late):.3f} ms, max "
        f"{1e3 * late.max():.3f} ms over {len(late)} submits; compiles "
        f"in the window {in_window}; preemptions {stats.preemptions} "
        f"(streams restarted {records.preempted}); engine steps "
        f"{stats.steps}, prefill chunks {stats.prefill_chunks}, kernel "
        f"splits {engine.kernel_splits}")
    integrity = stats.integrity_failures
    del engine, params, served_model, model
    gc.collect()

    summary = None
    if trace:
        from bench import trace as trace_mod
        summary = trace_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = RunData(cell, dims, peaks, chips, setup_s, records, summary)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.metric_reader(m["name"], cell.bench).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    due = records.due_in_window()
    log(f"requests due in the window {len(due)}: first token by the end "
        f"{sum(1 for r in due if r.token_t)}, finished "
        f"{sum(1 for r in due if r.finished)}")
    ttft = [(r.token_t[0] if r.token_t else records.t_end) - r.due
            for r in due]
    itl = [b - a for r in records.requests
           for a, b in zip(r.token_t, r.token_t[1:]) if records.in_window(b)]
    if ttft and itl:
        log("ttft ms p50/p75/p90 " + "/".join(
            f"{1e3 * np.percentile(ttft, q):.1f}" for q in (50, 75, 90))
            + f" over {len(ttft)}; itl ms p50/p95/p99 " + "/".join(
            f"{1e3 * np.percentile(itl, q):.1f}" for q in (50, 95, 99))
            + f" over {len(itl)}")

    lim = wl["check"]
    picked = check.sample(records, seed, lim["sample_tokens"],
                          lim["sample_requests"])
    t = time.perf_counter()
    gap, gap_c = check.gaps(picked, key, cell.reference, dims,
                            eng_cfg["max_len"],
                            int(cell.mix["output_tokens"]["max"]), control)
    log(f"reference over {len(picked)} requests, "
        f"{sum(r.out_len for r in picked)} served tokens: "
        f"{time.perf_counter() - t:.3f} s")
    if control:
        # the control stands in the program's place: its gap is the one
        # compared, and the program's is only logged
        log(f"program max_logit_gap (not compared): {gap}")
        gap_p, gap = gap, gap_c
    checks = {
        "max_logit_gap": {"value": gap, "limit": lim["max_logit_gap"]},
        "bad_requests": {
            "value": check.bad_requests(records, dims.vocab, integrity),
            "limit": 0},
    }
    correct = (gap is not None and gap <= lim["max_logit_gap"]
               and checks["bad_requests"]["value"] == 0)
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    # a request still queued when the run ends has not failed: above the
    # knee the queue grows through the window by design
    out = {"correct": bool(correct), "attempted": len(due),
           "failed": sum(check.is_bad(r, dims.vocab) for r in due),
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        gaps = sorted(summary.idle_gaps(), key=lambda g: -g[1])[:10]
        out["breakdown"] = {"device_ops": [list(x) for x in
                                           summary.top_ops(10)],
                            "idle_gaps": [list(x) for x in gaps]}
    if control:
        out["program_max_logit_gap"] = gap_p
    out["checks"] = checks
    return out

