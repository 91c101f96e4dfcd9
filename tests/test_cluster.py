"""Multi-replica cluster invariants on the deterministic sim harness.

The exact-trace contracts the ISSUE's cluster tier has to honor:

* one replica is a no-op wrapper — byte-identical tokens and timestamps
  vs a bare paged engine on the same scripted trace;
* the scheduler's new ``requeue_policy`` hook defaults to the old
  unconditional front-requeue (single-replica behavior byte-identical),
  and a hook that declines (returns False) changes nothing;
* at two-plus replicas every admitted token is conserved under
  preemption + cross-replica re-route, and every request's tokens stay
  the greedy-exact ``expected_tokens`` sequence regardless of where it
  bounced;
* on the skewed trace the cost-aware policy strictly beats round-robin
  on cluster wall time and p99 latency (exact virtual-clock numbers);
* the router's bookkeeping (routed counts, shed, reroute caps) and the
  cluster telemetry merge are pinned.
"""
import numpy as np
import pytest

from repro.serve import PagedServingEngine
from repro.serve.cluster import (CostAwarePolicy, LeastLoadedPolicy,
                                 RoundRobinPolicy, Router, ServingCluster,
                                 make_policy, predicted_queue_seconds,
                                 serve_trace, skewed_trace, unit_latency)
from repro.serve.scheduler import ChunkedPrefillScheduler
from repro.serve.sim import (FakeCostModel, FakeModel, SimClock, drive,
                             expected_tokens)

VOCAB = 97
STEP = unit_latency(decode_s=0.5, chunk_s=0.25, overhead_s=0.01)


def build_cluster(n, policy="cost_aware", clock=None, shed_wait_s=None,
                  telemetry=None, **kw):
    """A simulated cluster: ``n`` FakeModel replicas sharing the host's
    device on one SimClock (``ServingCluster.build`` would give each
    replica a device of its own)."""
    clock = clock if clock is not None else SimClock()
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("n_blocks", 24)
    kw.setdefault("block_size", 8)
    kw.setdefault("chunk_size", 8)
    kw.setdefault("cost_model", FakeCostModel(decode_s=0.5, prefill_s=0.25))
    replicas = [PagedServingEngine(
        FakeModel(vocab=VOCAB), None, clock=clock,
        telemetry=telemetry.controller(i) if telemetry else None, **kw)
        for i in range(n)]
    cl = ServingCluster(replicas, policy=policy, shed_wait_s=shed_wait_s,
                        telemetry=telemetry)
    return cl, clock


def run_trace(cl, clock, trace):
    return serve_trace(cl, trace, clock, step_seconds=STEP, min_dt=0.25)


TRACE = skewed_trace(12, vocab=VOCAB, period=2, long_len=24, short_len=4,
                     long_new=12, short_new=4, interval_s=1.0, load=2.0)


# ---------------------------------------------------------------------------
# replica_count=1: the cluster is a transparent wrapper
# ---------------------------------------------------------------------------


def test_single_replica_byte_identical_to_bare_engine():
    cl, clock = build_cluster(1)
    admitted = run_trace(cl, clock, TRACE)
    assert len(cl.done) == len(TRACE) and cl.stats.shed == 0

    clock2 = SimClock()
    eng = PagedServingEngine(FakeModel(vocab=VOCAB), None, max_batch=4,
                             max_len=64, n_blocks=24, block_size=8,
                             chunk_size=8, clock=clock2,
                             cost_model=FakeCostModel(decode_s=0.5,
                                                      prefill_s=0.25))
    rids = drive(eng, clock2, TRACE, dt=0.5)
    assert len(eng.done) == len(TRACE)
    # crids and rids both enumerate the trace in arrival order
    for (crid, t_c), (rid, t_b) in zip(sorted(admitted.items()),
                                       sorted(rids.items())):
        assert t_c == t_b
        assert list(cl.done[crid].tokens) == list(eng.done[rid].tokens)
        # stamped at the admitting tick, never before the arrival
        assert cl.done[crid].submitted_s >= t_c


def test_single_replica_tokens_greedy_exact():
    cl, clock = build_cluster(1)
    run_trace(cl, clock, TRACE)
    for crid in cl.done:
        _, prompt, new, eos = TRACE[crid]
        assert list(cl.done[crid].tokens) == expected_tokens(
            prompt, new, VOCAB, eos)


# ---------------------------------------------------------------------------
# requeue_policy: default + declining hook are byte-identical (regression
# for the unconditional-front-requeue fix)
# ---------------------------------------------------------------------------


def _run_bare(requeue_policy, probe):
    clock = SimClock()
    eng = PagedServingEngine(FakeModel(vocab=VOCAB), None, max_batch=4,
                             max_len=48, n_blocks=8, block_size=8,
                             chunk_size=8, clock=clock)
    if requeue_policy is not None:
        eng.scheduler.requeue_policy = requeue_policy
    trace = skewed_trace(8, vocab=VOCAB, period=2, long_len=24, short_len=4,
                         long_new=12, short_new=4, interval_s=1.0, load=4.0)
    rids = drive(eng, clock, trace, dt=0.5, max_steps=2000)
    assert eng.stats.preemptions > 0, "trace must exercise the requeue path"
    if probe is not None:
        assert probe["calls"] == eng.stats.preemptions
    return [(rid, list(eng.done[rid].tokens), eng.done[rid].finished_s)
            for rid in sorted(eng.done)]


def test_requeue_policy_default_and_declining_hook_identical():
    baseline = _run_bare(None, None)
    probe = {"calls": 0}

    def decline(req):
        probe["calls"] += 1
        return False

    assert _run_bare(decline, probe) == baseline


def test_requeue_policy_claim_removes_from_queue():
    sched = ChunkedPrefillScheduler(chunk_size=8)

    class Req:
        prompt = np.arange(4)
        max_new_tokens = 2
    claimed = []
    sched.requeue_policy = lambda r: claimed.append(r) is None
    sched.requeue(Req())
    assert len(claimed) == 1 and len(sched.queue) == 0
    sched.requeue_policy = lambda r: False
    sched.requeue(Req())
    assert len(sched.queue) == 1


# ---------------------------------------------------------------------------
# replica_count>=2: conservation under preemption + re-route
# ---------------------------------------------------------------------------


def tight_trace(n=10):
    # pools of 8x8-token blocks per replica: a long request needs 5, so
    # concurrent longs evict each other -> preemptions + reroute chances
    return skewed_trace(n, vocab=VOCAB, period=2, long_len=24, short_len=4,
                        long_new=12, short_new=4, interval_s=1.0, load=4.0)


@pytest.mark.parametrize("policy", ["round_robin", "least_loaded",
                                    "cost_aware"])
def test_tokens_conserved_under_preemption_and_reroute(policy):
    cl, clock = build_cluster(2, policy=policy, max_len=48, n_blocks=8)
    trace = tight_trace()
    admitted = run_trace(cl, clock, trace)
    assert sum(e.stats.preemptions for e in cl.replicas) > 0
    assert len(cl.done) == len(admitted) == len(trace)
    total = 0
    for crid in cl.done:
        _, prompt, new, eos = trace[crid]
        assert list(cl.done[crid].tokens) == expected_tokens(
            prompt, new, VOCAB, eos)
        total += len(cl.done[crid].tokens)
    assert total == sum(len(expected_tokens(p, n, VOCAB, e))
                        for _, p, n, e in trace)


def test_cost_aware_reroutes_and_tokens_survive_the_move():
    cl, clock = build_cluster(2, policy="cost_aware", max_len=48, n_blocks=8)
    trace = tight_trace()
    run_trace(cl, clock, trace)
    assert cl.stats.reroutes > 0, "tight pools must trigger a re-route"
    assert cl.stats.reroutes + cl.stats.front_requeues == sum(
        e.stats.preemptions for e in cl.replicas)
    for crid in cl.done:      # the moved requests still decode exactly
        _, prompt, new, eos = trace[crid]
        assert list(cl.done[crid].tokens) == expected_tokens(
            prompt, new, VOCAB, eos)


def test_round_robin_never_reroutes():
    cl, clock = build_cluster(2, policy="round_robin", max_len=48,
                              n_blocks=8)
    run_trace(cl, clock, tight_trace())
    assert cl.stats.reroutes == 0
    assert cl.stats.front_requeues == sum(e.stats.preemptions
                                          for e in cl.replicas)


# ---------------------------------------------------------------------------
# the campaign's headline: cost-aware beats round-robin on the skewed
# trace, in exact virtual-clock arithmetic
# ---------------------------------------------------------------------------


def test_cost_aware_beats_round_robin_on_skewed_trace():
    results = {}
    for policy in ("round_robin", "cost_aware"):
        cl, clock = build_cluster(2, policy=policy)
        admitted = run_trace(cl, clock, TRACE)
        lats = sorted(cl.done[c].finished_s - admitted[c] for c in cl.done)
        results[policy] = {
            "wall": clock.t,
            "p99": lats[int(0.99 * (len(lats) - 1))],
            "tokens": {c: list(cl.done[c].tokens) for c in cl.done},
        }
    rr, ca = results["round_robin"], results["cost_aware"]
    assert ca["wall"] < rr["wall"]          # higher tok/s, same tokens
    assert ca["p99"] < rr["p99"]
    assert ca["tokens"] == rr["tokens"]     # placement is not semantics


# ---------------------------------------------------------------------------
# router bookkeeping
# ---------------------------------------------------------------------------


def test_router_shed_and_routed_accounting():
    cl, clock = build_cluster(2, policy="round_robin", shed_wait_s=3.0)
    trace = skewed_trace(16, vocab=VOCAB, period=2, long_len=24,
                         short_len=4, long_new=12, short_new=4,
                         interval_s=1.0, load=8.0)
    admitted = run_trace(cl, clock, trace)
    st = cl.stats
    assert st.shed > 0 and st.submitted == len(admitted)
    assert st.shed + st.submitted == len(trace)
    assert sum(st.routed) >= st.submitted   # routed counts re-routes too
    assert len(cl.done) == len(admitted)    # shed requests are refused,
    #                                         admitted ones all finish


def test_router_refuses_double_ownership_and_unknown_policy():
    cl, _ = build_cluster(2)
    with pytest.raises(ValueError):
        Router(cl.replicas, policy="round_robin")
    with pytest.raises(ValueError):
        make_policy("nope")


def test_reroute_cap_limits_ping_pong():
    cl, clock = build_cluster(2, policy="cost_aware", max_len=48,
                              n_blocks=8)
    cl.router.max_reroutes = 0
    run_trace(cl, clock, tight_trace())
    assert cl.stats.reroutes == 0           # cap forces front-requeue
    assert len(cl.done) == len(tight_trace())


def test_router_bookkeeping_drains_and_leaks_are_loud():
    # regression for the drain-audit sweep: after every admitted request
    # finishes, the rid maps and the in-flight move set must be EMPTY —
    # and a leaked entry must fail the assert with the dict named
    cl, clock = build_cluster(2, policy="cost_aware", max_len=48,
                              n_blocks=8)
    run_trace(cl, clock, tight_trace())
    assert cl.stats.reroutes > 0     # the trace must exercise _moves
    cl.router.assert_drained()
    cl.router._moves[999] = 0
    with pytest.raises(AssertionError, match="_moves"):
        cl.router.assert_drained()


def test_predicted_queue_seconds_empty_and_loaded():
    cl, _ = build_cluster(1)
    eng = cl.replicas[0]
    assert predicted_queue_seconds(eng) == 0.0
    eng.submit(np.arange(8, dtype=np.int32), max_new_tokens=4)
    # 1 chunk * 0.25s + 4 tokens * (0.5s / 4 rows)
    assert predicted_queue_seconds(eng) == pytest.approx(0.75)


def test_policy_place_prefers_empty_replica():
    cl, _ = build_cluster(2)
    cl.replicas[0].submit(np.arange(8, dtype=np.int32), max_new_tokens=8)
    for policy in (LeastLoadedPolicy(), CostAwarePolicy()):
        assert policy.place(4, 4, cl.replicas) == 1
    assert RoundRobinPolicy().place(4, 4, cl.replicas) == 0


# ---------------------------------------------------------------------------
# cluster telemetry: per-replica controllers, merged views
# ---------------------------------------------------------------------------


def test_cluster_telemetry_merge_and_tags(tmp_path):
    from repro.serve.cluster import ClusterTelemetry
    from repro.serve.sim import work_latency_model
    tel = ClusterTelemetry(2, latency_model=work_latency_model(0.5, 0.25))
    cl, clock = build_cluster(2, policy="round_robin", telemetry=tel)
    run_trace(cl, clock, TRACE)
    s = tel.summary()
    assert s["n_replicas"] == 2 and len(s["per_replica"]) == 2
    assert s["requests"] == len(TRACE)
    assert s["latency_p99_s"] >= s["latency_p50_s"] > 0
    lines = tel.export_jsonl(tmp_path / "cluster.jsonl").read_text()
    import json
    tags = {json.loads(ln)["replica"] for ln in lines.splitlines()}
    assert tags == {0, 1}


def test_build_from_device_budget_uses_cost_model_topology():
    import jax
    from repro.configs.base import ShapeCell
    from repro.core.costmodel import CostModel
    from repro.sharding.plans import rank_cluster_topologies
    model = FakeModel(vocab=VOCAB)
    cm = CostModel.from_named("tpu_v5e")
    cell = ShapeCell("t", "decode", 64, 4)
    kw = dict(clock=SimClock(), cost_model=cm, cell=cell, max_batch=4,
              max_len=64, n_blocks=24, block_size=8, chunk_size=8)
    n_dev = len(jax.devices())
    cluster = ServingCluster.build(model, None, n_devices=n_dev, **kw)
    top = rank_cluster_topologies(model.cfg, cell, n_dev, cm)[0]
    assert cluster.topology is not None
    assert len(cluster.replicas) == top.n_replicas
    assert cluster.topology.devices_per_replica * top.n_replicas == n_dev
    # every replica's pool sits on its own slice of the devices
    placed = [tuple(sorted(d.id for d in eng.cache["k"].devices()))
              for eng in cluster.replicas]
    assert len(set(placed)) == len(placed)
    # a budget beyond the devices present is refused, not simulated
    with pytest.raises(ValueError, match="exceeds"):
        ServingCluster.build(model, None, n_devices=4 * n_dev, **kw)
    with pytest.raises(ValueError, match="exceeds"):
        ServingCluster.build(model, None, n_replicas=n_dev + 1, **kw)
    with pytest.raises(ValueError):
        ServingCluster.build(model, None)   # neither n_replicas nor budget


# ---------------------------------------------------------------------------
# sharding CLI (satellite): ranked factorization table
# ---------------------------------------------------------------------------


def test_sharding_cli_prints_ranked_tables(capsys):
    from repro.sharding.cli import main
    rc = main(["--calibration", "tpu_v5e", "--topology", "4,8,128",
               "--devices", "4"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "data=" in out and "<- best" in out
    assert "replicas=" in out


def test_sharding_cli_rejects_bad_topology():
    from repro.sharding.cli import main
    with pytest.raises(SystemExit):
        main(["--topology", "8,8"])


# ---------------------------------------------------------------------------
# bench schema v4 round-trip + trajectory pickup
# ---------------------------------------------------------------------------


def test_bench_v5_validate_and_compare_scenarios(tmp_path):
    import importlib.util
    import json
    import sys
    root = __import__("pathlib").Path(__file__).resolve().parent.parent
    for name, rel in (("bench_serve", "benchmarks/bench_serve.py"),
                      ("traj_compare", "benchmarks/trajectory/compare.py")):
        spec = importlib.util.spec_from_file_location(name, root / rel)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    bench, comp = sys.modules["bench_serve"], sys.modules["traj_compare"]

    assert bench.SCHEMA == "bench_serve/v6" and bench.BENCH_ID == 10
    doc = {"schema": bench.SCHEMA, "bench_id": 10, "engines": {},
           "cluster": {"r1": {"rr_tok_per_s": 10.0, "ca_tok_per_s": 11.0},
                       "r2": {"rr_tok_per_s": 17.0, "ca_tok_per_s": 20.0}},
           "sharded": {"ref_step_s": 0.5, "d1m1_step_s": 0.5,
                       "d1m1_pred_step_s": 1e-6, "d2m2_step_s": 0.25},
           "chaos": {"crash": {"ok": True, "tokens_lost": 0}}}
    path = tmp_path / "BENCH_10.json"
    path.write_text(json.dumps(doc))
    loaded = bench.validate_bench_doc(json.loads(path.read_text()))
    assert loaded == doc                                 # round-trip
    s = comp.scenarios(loaded)
    assert s["cluster.r1.rr"] == 10.0 and s["cluster.r2.ca"] == 20.0
    # sharded step times gate as inverted rates; predictions are
    # diagnostics, not gated scenarios
    assert s["sharded.d1m1.steps_per_s"] == 2.0
    assert s["sharded.d2m2.steps_per_s"] == 4.0
    assert s["sharded.ref.steps_per_s"] == 2.0
    assert not any("pred" in k for k in s)
    # older schemas still validate (blocks only required from their
    # introducing version on)
    bench.validate_bench_doc({"schema": "bench_serve/v3", "engines": {}})
    bench.validate_bench_doc({"schema": "bench_serve/v4", "engines": {},
                              "cluster": {}})
    with pytest.raises(ValueError):
        bench.validate_bench_doc({"schema": "bench_serve/v4",
                                  "engines": {}})        # missing cluster
    with pytest.raises(ValueError):
        bench.validate_bench_doc({"schema": "bench_serve/v5",
                                  "engines": {},
                                  "cluster": {}})        # missing sharded
    with pytest.raises(ValueError):
        bench.validate_bench_doc({"schema": "bench_serve/v6",
                                  "engines": {}, "cluster": {},
                                  "sharded": {}})        # missing chaos
    with pytest.raises(ValueError):
        bench.validate_bench_doc({"schema": "bench_serve/v99",
                                  "engines": {}, "cluster": {},
                                  "sharded": {}, "chaos": {}})
    with pytest.raises(ValueError):
        bench.validate_bench_doc({"schema": "autotune.cache/v1"})


def test_committed_trajectory_carries_bench9_sharded():
    import importlib.util
    import sys
    root = __import__("pathlib").Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "traj_compare3", root / "benchmarks/trajectory/compare.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["traj_compare3"] = mod
    spec.loader.exec_module(mod)
    traj = mod.load_trajectory(root / "benchmarks/trajectory")
    ids = [i for i, _ in traj]
    assert 9 in ids, "BENCH_9.json must be committed with this change"
    doc = dict(traj)[9]
    assert doc["schema"] == "bench_serve/v5"
    assert doc["sharded_ok"] and doc["identical_tokens"]
    sh = doc["sharded"]
    assert sh["identical_all"]
    for d, m in ((1, 1), (2, 1), (1, 2), (2, 2)):
        assert sh[f"d{d}m{m}_identical"], (d, m)
        assert sh[f"d{d}m{m}_sync_ok"] and sh[f"d{d}m{m}_donated"], (d, m)
        assert sh[f"d{d}m{m}_pred_step_s"] > 0, (d, m)
    assert mod.compare(traj, tolerance=0.6) == []


def test_committed_trajectory_carries_bench10_chaos():
    import importlib.util
    import sys
    root = __import__("pathlib").Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "traj_compare4", root / "benchmarks/trajectory/compare.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["traj_compare4"] = mod
    spec.loader.exec_module(mod)
    traj = mod.load_trajectory(root / "benchmarks/trajectory")
    ids = [i for i, _ in traj]
    assert 10 in ids, "BENCH_10.json must be committed with this change"
    doc = dict(traj)[10]
    assert doc["schema"] == "bench_serve/v6"
    assert doc["chaos_ok"] and doc["identical_tokens"]
    for fault in ("crash", "hang", "corrupt", "crashloop"):
        m = doc["chaos"][fault]
        assert m["ok"], fault
        assert m["survivors_identical"] and m["all_accounted"], fault
        assert m["tokens_lost"] == 0 and m["blocks_leaked"] == 0, fault
    assert doc["chaos"]["crashloop"]["quarantined"]
    # the chaos block is invisible to the tok/s trajectory gate
    assert not any(k.startswith("chaos") for k in mod.scenarios(doc))
    assert mod.compare(traj, tolerance=0.6) == []


def test_committed_trajectory_carries_bench8_cluster():
    import importlib.util
    import sys
    root = __import__("pathlib").Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "traj_compare2", root / "benchmarks/trajectory/compare.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["traj_compare2"] = mod
    spec.loader.exec_module(mod)
    traj = mod.load_trajectory(root / "benchmarks/trajectory")
    ids = [i for i, _ in traj]
    assert 8 in ids, "BENCH_8.json must be committed with this change"
    doc = dict(traj)[8]
    assert doc["schema"] == "bench_serve/v4"
    assert doc["cluster_ok"] and doc["identical_tokens"]
    m = doc["cluster"]["r2"]
    assert m["speedup_tok_s"] > 1.0 and m["p99_ratio"] > 1.0, \
        "cost-aware placement must beat round-robin in the snapshot"
    assert mod.compare(traj, tolerance=0.6) == []


# ---------------------------------------------------------------------------
# topology ranking
# ---------------------------------------------------------------------------


def test_rank_cluster_topologies_orders_and_factors():
    from repro.configs import ARCHS, reduced
    from repro.configs.base import ShapeCell
    from repro.core.costmodel import CostModel
    from repro.sharding.plans import rank_cluster_topologies
    cfg = reduced(ARCHS["gemma2-2b"], n_layers=2, vocab_size=128)
    cell = ShapeCell("t", "decode", 128, 8)
    cm = CostModel.from_named("tpu_v5e")
    tops = rank_cluster_topologies(cfg, cell, 8, cm)
    assert [t.predicted_tok_s for t in tops] == sorted(
        (t.predicted_tok_s for t in tops), reverse=True)
    for t in tops:
        assert 8 % t.n_replicas == 0
        assert t.devices_per_replica * t.n_replicas == 8
        assert t.predicted_tok_s == pytest.approx(
            t.n_replicas * cell.global_batch / t.plan.step_s)
    assert rank_cluster_topologies(cfg, cell, 8, cm, max_replicas=1)[
        0].n_replicas == 1
