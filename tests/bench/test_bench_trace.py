"""The trace reduction on a small synthesized trace: busy union, idle
gaps named after the host span they fell in, per-program time, and the
kernel found by name."""
import pytest

from bench import trace

# device 0: op [2,7] (a loop) holds [4,6] (the kernel), [9,10] is
# apart, [0,1] lies before the window; programs: two decode launches,
# one chunk launch.
# host: the window [1.5, 11], a step over [1, 8], a wait over [7.5, 10].
# times in the proto are ns and ps; the test reads seconds * 1e-6.
_OPS = [(1, 2, 5), (2, 4, 2), (3, 9, 1), (1, 0, 1)]
_MODULES = [(4, 2, 5), (4, 9, 1), (5, 4, 1)]
_HOST = [(6, 1.5, 9.5), (7, 1, 7), (8, 7.5, 2.5)]


def _events(rows):
    return "\n".join(
        f"    events {{ metadata_id: {m} offset_ps: {int(t * 1e6)} "
        f"duration_ps: {int(d * 1e6)} }}" for m, t, d in rows)


PROTO = f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{_events(_OPS)}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
{_events(_MODULES)}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2
    name: "%_pa_jit.3 = bf16[4] custom-call()" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "copy.2" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "jit_fused_decode(7)" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "jit_fused_chunk(8)" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{_events(_HOST)}
  }}
  event_metadata {{ key: 6 value {{ id: 6 name: "bench.window" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "bench.step" }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "bench.wait" }} }}
}}
"""

US = 1e-6


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    return trace.summarize(ProfileData.from_text_proto(PROTO))


def test_window_and_busy_union(summary):
    assert summary.n_devices == 1
    assert summary.window == pytest.approx((1.5 * US, 11 * US))
    # ops clipped to the window: [2,7] (a loop and its body) and [9,10]
    assert summary.busy_s() == pytest.approx(6 * US)


def test_idle_gaps_named_by_host_span(summary):
    gaps = summary.idle_gaps()
    assert [g[0] for g in gaps] == ["bench.step", "bench.wait", "none"]
    assert [g[1] for g in gaps] == pytest.approx([0.5 * US, 2 * US, 1 * US])


def test_program_and_kernel_time(summary):
    n, s = summary.module_time("fused_decode")
    assert n == 2 and s == pytest.approx(6 * US)
    n, s = summary.module_time("fused_chunk")
    assert n == 1 and s == pytest.approx(1 * US)
    n, s = summary.op_time(("%_pa_jit",))
    assert n == 1 and s == pytest.approx(2 * US)
    # self time: the loop's 5 less its body's 2, the kernel's 2, the copy's 1
    top = summary.top_ops(3)
    assert [t[0] for t in top] == ["fusion.1", "%_pa_jit.3", "copy.2"]
    assert [t[1] for t in top] == pytest.approx([3 * US, 2 * US, 1 * US])


def test_union_merges_overlaps():
    assert trace.union([(3, 4), (0, 2), (1, 3), (6, 7)]) == [(0, 4), (6, 7)]


def test_trace_without_window_is_refused():
    from jax.profiler import ProfileData
    with pytest.raises(ValueError):
        trace.summarize(ProfileData.from_text_proto(
            PROTO.replace('"bench.window"', '"other"')))
