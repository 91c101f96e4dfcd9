"""The program's spans and scopes in a small synthesized trace: host work
per step with the sync taken out, decode rows per step, the kernel found
by its scope, idle gaps named after the innermost span; the readers
return None on a trace without them, and every reader the benchmark
already had reads what it read before."""
import time

import numpy as np
import pytest
from conftest import TINY_MODEL, tiny_cell
from jax.profiler import ProfileData

from bench import harness, program_trace, spec, trace
from bench.references.dense_gqa import model_dims
from bench.serve_loop import Records, Tracked

# device 0 (times in us): a loop [2, 9] holds the kernel [3, 5] and its
# output reduce [5, 5.5], both under the scope; the copy [9.5, 10] is
# outside it.  The scope path is a stat on the kernel's and the reduce's
# metadata, as on a TPU: as a string and as a reference to a stat
# metadata's name.  Programs: two decode launches, one chunk launch.
# host: the window [1.5, 11]; bench.step [1.2, 6] and [6, 10.8]; inside
# them serve.step [1.6, 5.9] (rows 3) holding serve.sync [4, 5.5], and
# serve.step [6.1, 10.7] (rows 0) holding no sync but serve.chunk
# [6.5, 9.5], which holds serve.launch [7.5, 9.2]; a serve.step
# [0.2, 1.0] before the window (rows 5).
_OPS = [(1, 2, 7, None), (2, 3, 2, None), (3, 5, 0.5, None),
        (4, 9.5, 0.5, None)]
_MODULES = [(5, 2, 5, None), (5, 9, 1, None), (6, 4, 1, None)]
_HOST = [(7, 1.5, 9.5, None), (8, 1.2, 4.8, None), (8, 6, 4.8, None),
         (9, 1.6, 4.3, 3), (10, 4, 1.5, None), (9, 6.1, 4.6, 0),
         (9, 0.2, 0.8, 5), (11, 6.5, 3, None), (12, 7.5, 1.7, None)]
SCOPE = ("jit(fused_decode)/while/body/closed_call/paged_attention/"
         "jit(_pa_jit)/pallas_call")


def _events(rows):
    out = []
    for m, t, d, extra in rows:
        stats = ""
        if extra is not None:
            stats = f"stats {{ metadata_id: 20 int64_value: {extra} }}"
        out.append(f"    events {{ metadata_id: {m} offset_ps: "
                   f"{int(t * 1e6)} duration_ps: {int(d * 1e6)} {stats} }}")
    return "\n".join(out)


def _proto(scope_stat="tf_op"):
    """The trace, its scope paths in the metadata stat ``scope_stat``."""
    md_stat = [f'stats {{ metadata_id: 22 str_value: "{SCOPE}" }}',
               "stats { metadata_id: 22 ref_value: 23 }"]
    return f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{_events(_OPS)}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
{_events(_MODULES)}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%while.1 = (s32[]) while()" }} }}
  event_metadata {{ key: 2 value {{ id: 2
    name: "%_pa_jit.3 = bf16[4] custom-call()" {md_stat[0]} }} }}
  event_metadata {{ key: 3 value {{ id: 3
    name: "%reduce.4 = bf16[4] reduce()" {md_stat[1]} }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%copy.2 = bf16[4] copy()" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "jit_fused_decode(7)" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "jit_fused_chunk(8)" }} }}
  stat_metadata {{ key: 22 value {{ id: 22 name: "{scope_stat}" }} }}
  stat_metadata {{ key: 23 value {{ id: 23 name: "{SCOPE}" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{_events(_HOST)}
  }}
  event_metadata {{ key: 7 value {{ id: 7 name: "bench.window" }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "bench.step" }} }}
  event_metadata {{ key: 9 value {{ id: 9 name: "serve.step" }} }}
  event_metadata {{ key: 10 value {{ id: 10 name: "serve.sync" }} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "serve.chunk" }} }}
  event_metadata {{ key: 12 value {{ id: 12 name: "serve.launch" }} }}
  stat_metadata {{ key: 20 value {{ id: 20 name: "rows" }} }}
}}
"""


US = 1e-6


def _summary(proto, attach=True):
    """The summary ``bench/trace.py`` makes, or with the program's part
    (``program``) when ``attach``."""
    raw = ProfileData.text_proto_to_serialized_xspace(proto)
    if attach:
        return program_trace.summarize_with_program(raw)
    return trace.summarize(ProfileData.from_serialized_xspace(raw))


def _run(tr):
    """What a reader is given: two requests whose tokens reach the host
    inside the window (host clock [0, 10]), one past it."""
    reqs = [Tracked(0, None, 0.0, np.zeros(4, np.int32), 3,
                    token_t=[1.0, 2.0, 3.0]),
            Tracked(1, None, 0.5, np.zeros(6, np.int32), 3,
                    token_t=[2.0, 3.0, 11.0])]
    rec = Records(t0=0.0, t1=10.0, t_end=12.0, requests=reqs, steps=[],
                  lateness=[0.0, 0.0])
    return harness.RunData(tiny_cell(), model_dims(TINY_MODEL),
                           spec.load_peaks("TPU v5 lite"), 1, 1.0, rec, tr)


def _read(metric, tr):
    return spec.metric_reader(metric).read(_run(tr))


def test_spans_and_scopes_are_read_in_the_window():
    pt = _summary(_proto()).program
    assert [s.name for s in pt.spans] == ["serve.step", "serve.sync",
                                          "serve.step", "serve.chunk",
                                          "serve.launch"]
    steps = pt.steps()
    assert [s.stats for s in steps] == [{"rows": 3}, {"rows": 0}]
    assert pt.scopes == {"%_pa_jit.3 = bf16[4] custom-call()": SCOPE,
                         "%reduce.4 = bf16[4] reduce()": SCOPE}
    assert pt.in_scope("%reduce.4 = bf16[4] reduce()", "paged_attention")
    assert not pt.in_scope("%copy.2 = bf16[4] copy()", "paged_attention")


def test_host_step_takes_the_waits_out_and_counts_window_steps():
    # (4.3 - 1.5 sync) and (4.6 - 1.7 launch) us: the step before the
    # window is left out
    assert _read("host_step_ms", _summary(_proto())) == pytest.approx(
        1e3 * (2.8 + 2.9) / 2 * US)
    pt = _summary(_proto()).program
    got = [[r[k] for k in ("host", "serve.sync", "serve.launch")]
           for r in pt.split()]
    assert got == [pytest.approx([2.8 * US, 1.5 * US, 0.0]),
                   pytest.approx([2.9 * US, 0.0, 1.7 * US])]


def test_decode_rows_counts_steps_that_dispatched_a_decode():
    # the window's steps step 3 and 0 rows; the one before it (5) is out
    assert _read("decode_rows", _summary(_proto())) == 3


def test_kernel_is_found_by_its_scope():
    # the kernel [3, 5] and its reduce [5, 5.5] over two decode launches
    assert _read("paged_attention_ms", _summary(_proto())) == \
        pytest.approx(1e3 * 2.5 * US / 2)


def test_kernel_without_a_scope_reads_none():
    tr = _summary(_proto(scope_stat="hlo_category"))
    assert tr.program.scopes == {}
    assert _read("paged_attention_ms", tr) is None


@pytest.mark.parametrize("metric", ["host_step_ms", "decode_rows",
                                    "paged_attention_ms"])
def test_readers_return_none_without_the_programs_part(metric):
    """A summary as ``bench/trace.py`` makes it (no ``program``), and a
    program without spans or scopes, read as no value."""
    assert _read(metric, _summary(_proto(), attach=False)) is None
    assert _read(metric, None) is None


def test_idle_gaps_name_the_innermost_span():
    gaps = _summary(_proto()).idle_gaps()
    # [1.5, 2]: bench.step covers 0.5 of it, serve.step only 0.4;
    # [9, 9.5]: covered whole by bench.step, serve.step and serve.chunk,
    # the innermost (serve.launch covers only 0.2); [10, 11]: bench.step
    # 0.8, serve.step 0.7
    assert [g[0] for g in gaps] == ["bench.step", "serve.chunk",
                                    "bench.step"]
    assert [g[1] for g in gaps] == pytest.approx([0.5 * US, 0.5 * US,
                                                  1 * US])
    # the benchmark's own naming sees only its spans
    assert [g[0] for g in _summary(_proto(), attach=False).idle_gaps()] \
        == ["bench.step"] * 3


EXISTING = ["engine_step_ms", "decode_step_ms", "chunk_step_ms",
            "paged_attention_roofline", "device_idle_pct", "step_mfu_pct"]


def _recorded():
    from test_bench_trace import PROTO
    return PROTO


@pytest.mark.parametrize("attach", [False, True],
                         ids=["as_summarized", "program_attached"])
@pytest.mark.parametrize("metric,want", [
    ("engine_step_ms", None),               # no bench.step begins in it
    ("decode_step_ms", 6 * US * 1e3 / 2),
    ("chunk_step_ms", 1 * US * 1e3),
    ("paged_attention_roofline", 0.37509157509157515),
    ("device_idle_pct", 100 * (1 - 6 / 9.5)),
    ("step_mfu_pct", 0.11252236174191825)])
def test_existing_readers_pinned_on_the_recorded_trace(metric, want,
                                                       attach):
    """The readers the benchmark already had, on the recorded trace of
    ``test_bench_trace.py``, read these values, whether or not the
    program's part is attached to the summary."""
    got = _read(metric, _summary(_recorded(), attach))
    assert got == (None if want is None else pytest.approx(want, rel=1e-12))


@pytest.mark.parametrize("metric", EXISTING)
def test_existing_readers_unchanged_by_the_programs_part(metric):
    assert _read(metric, _summary(_proto(), attach=True)) == \
        _read(metric, _summary(_proto(), attach=False))


def test_trace_program_run_reports_the_span_metrics(tiny):
    """A whole traced run on the CPU at a tiny size through
    ``bench/trace_program.py``: the span metrics are in the result line
    (the kernel's has no device trace to read here)."""
    import dataclasses

    from bench import trace_program
    cell = dataclasses.replace(
        tiny, per_layer=trace_program.program_metrics(tiny.name))
    out = trace_program.run(cell, 5, 2.0, t_start=time.perf_counter(),
                            require_tpu=False)
    m = out["metrics"]
    assert set(m) == {"host_step_ms.test", "decode_rows.test"}
    assert 0 < m["host_step_ms.test"]["value"]
    assert 1 <= m["decode_rows.test"]["value"] <= tiny.engine["max_batch"]
    c = out["program_checks"]
    assert abs(c["serve_steps"] - c["bench_steps"]) <= 1
    ms = c["step_ms"]
    assert ms["host"] == pytest.approx(m["host_step_ms.test"]["value"])
    assert ms["host"] + ms["serve.sync"] + ms["serve.launch"] == \
        pytest.approx(ms["serve.step"], rel=0.05)
    assert ms["serve.step"] <= c["engine_step_ms"]
