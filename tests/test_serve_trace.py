"""What the serving engines record about their own time: the paged
engine's spans in the profiler's trace (``serve.*``, with the rows
``serve.step`` carries), ``sync_s`` and ``first_token_s`` in the
telemetry records, and the paged kernel's scope in the decode step."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import ARCHS, get_config, reduced
from repro.models.zoo import build_model
from repro.serve import PagedServingEngine, ServingEngine
from repro.serve.engine import paged_decode_fn
from repro.serve.sim import FakeModel, SimClock
from repro.serve.telemetry import TelemetryController, spans


def _host_spans(tmp_path, fn):
    """Run ``fn`` under the profiler; the ``serve.*`` host spans as
    ``(name, start_ns, end_ns, stats)``, in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    prof = ProfileData.from_file(str(path))
    out = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
           for plane in prof.planes if plane.name.startswith("/host")
           for line in plane.lines for e in line.events
           if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: s[1])


def _inside(outer, spans_, name):
    return [s for s in spans_ if s[0] == name
            and outer[1] <= s[1] and s[2] <= outer[2]]


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """A fused paged engine serving five requests (no eos, a pool that
    never runs dry) under the profiler."""
    ctl = TelemetryController(drift=False)
    eng = PagedServingEngine(FakeModel(), params=None, max_batch=3,
                             max_len=32, block_size=4, chunk_size=4,
                             telemetry=ctl)
    rng = np.random.default_rng(3)

    def serve():
        for n in (3, 9, 1, 6, 12):
            eng.submit(rng.integers(0, 97, n), max_new_tokens=5)
        eng.run_until_done()

    got = _host_spans(tmp_path_factory.mktemp("trace"), serve)
    return eng, ctl, got


def _productive(steps, got):
    """The steps that ran a chunk or a decode, with their chunk count."""
    out = []
    for s in steps:
        chunks = len(_inside(s, got, spans.CHUNK))
        if chunks or s[3]["rows"]:
            out.append((s, chunks))
    return out


def test_every_step_is_one_span_with_the_engines_counts(traced_run):
    eng, ctl, got = traced_run
    steps = [s for s in got if s[0] == spans.STEP]
    assert all(set(s[3]) == {"rows"} for s in steps)
    productive = _productive(steps, got)
    records = ctl.sink.steps()
    assert len(productive) == len(records) == eng.stats.steps
    assert [(chunks, s[3]["rows"] > 0) for s, chunks in productive] == [
        (r.n_prefill_units, r.decode_ran) for r in records]
    # every decode row stepped once per token after the first
    assert sum(s[3]["rows"] for s in steps) == eng.stats.decoded_tokens


def test_one_sync_per_step_once_a_step_is_pending(traced_run):
    """On the fused path a step drains the decode the step before it
    dispatched: one ``serve.sync`` then, none otherwise."""
    eng, ctl, got = traced_run
    steps = [s for s in got if s[0] == spans.STEP]
    syncs = [len(_inside(s, got, spans.SYNC)) for s in steps]
    assert syncs == [0] + [int(s[3]["rows"] > 0) for s in steps[:-1]]
    # the records time the same waits
    productive = [len(_inside(s, got, spans.SYNC))
                  for s, _ in _productive(steps, got)]
    records = ctl.sink.steps()
    assert [r.sync_s > 0 for r in records] == [n > 0 for n in productive]
    assert all(r.sync_s <= r.measured_s for r in records)


def test_host_work_spans_nest_in_their_step(traced_run):
    eng, ctl, got = traced_run
    steps = [s for s in got if s[0] == spans.STEP]
    chunk_spans = [s for s in got if s[0] == spans.CHUNK]
    assert len(chunk_spans) == eng.stats.prefill_chunks
    assert sum(len(_inside(s, got, spans.CHUNK)) for s in steps) \
        == len(chunk_spans)
    assert len([s for s in got if s[0] == spans.RETIRE]) \
        == eng.stats.completed == 5
    for name in (spans.PLAN, spans.DECODE, spans.SYNC, spans.RETIRE,
                 spans.UPLOAD, spans.LAUNCH):
        inner = [s for s in got if s[0] == name]
        assert inner and sum(len(_inside(s, got, name))
                             for s in steps) == len(inner), name
    # each chunk, and each decode that steps rows, puts its operands up
    # once, then launches once
    decodes = [s for s in got if s[0] == spans.DECODE]
    n_decoding = sum(s[3]["rows"] > 0 for s in steps)
    for name in (spans.UPLOAD, spans.LAUNCH):
        assert [len(_inside(c, got, name)) for c in chunk_spans] == \
            [1] * len(chunk_spans), name
        assert sorted(len(_inside(d, got, name)) for d in decodes) == \
            [0] * (len(decodes) - n_decoding) + [1] * n_decoding, name


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = reduced(ARCHS["gemma2-2b"], n_layers=2, vocab_size=128)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "legacy"])
@pytest.mark.parametrize("engine", ["paged", "slot"])
def test_first_token_time_is_when_the_engine_books_it(tiny_lm, engine,
                                                      fused):
    """``first_token_s`` is the engine clock of the step in which the
    request's first token reached ``Request.tokens``, and the retirement
    record carries it."""
    clock = SimClock()
    ctl = TelemetryController(drift=False)
    if engine == "paged":
        eng = PagedServingEngine(FakeModel(), params=None, max_batch=2,
                                 max_len=32, block_size=4, chunk_size=4,
                                 clock=clock, fused=fused, telemetry=ctl)
    else:
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=2, max_len=32,
                            clock=clock, fused=fused, telemetry=ctl)
    rids = [eng.submit(np.arange(1, n, dtype=np.int32), max_new_tokens=4)
            for n in (3, 7, 10)]
    reqs = {}
    want = {}
    while eng.step() or len(eng.queue):
        live = [r for r in eng.queue] + [
            r.req for r in getattr(eng, "rows", []) if r is not None] + [
            r for r in getattr(eng, "slot_req", []) if r is not None]
        reqs.update((r.rid, r) for r in live)
        reqs.update(eng.done)
        for rid, r in reqs.items():
            if r.tokens and rid not in want:
                want[rid] = clock.t
        clock.advance(1.0)
    assert sorted(eng.done) == rids
    assert {rid: eng.done[rid].first_token_s for rid in rids} == want
    recs = {r.rid: r for r in ctl.sink.requests()}
    for rid in rids:
        rec = recs[rid]
        assert rec.first_token_s == want[rid]
        assert rec.submitted_s <= rec.first_token_s <= rec.finished_s


def test_paged_kernel_runs_under_its_scope(monkeypatch):
    """The paged-attention call in the decode step sits under the
    ``paged_attention`` scope: its ops' names carry it, whatever the
    kernel's wrapper is called (interpret mode, a tiny size)."""
    import repro.kernels.ops as kops
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kops, "_default_interpret", lambda: True)
    cfg = dataclasses.replace(
        get_config("internlm2-20b"), n_layers=1, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=128,
        use_pallas=True)
    model = build_model(cfg)
    B, bs, NB = 2, 8, 4
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = jax.eval_shape(lambda: model.init_paged_cache(B * NB, bs))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    text = jax.jit(paged_decode_fn(model.decode_step)).lower(
        params, pool, i32(B), i32(B), i32(B, NB)).as_text(debug_info=True)
    assert "paged_attention/jit(_pa_jit)" in text
