""":class:`ServingCluster` — N engine replicas behind one Router, with
the device budget optionally factorized by the cost model.

``build`` is the one-stop constructor: it can be told the replica count
directly, or handed a device budget + serving shape and let
``sharding.rank_cluster_topologies`` choose — the same calibrated
pricing that ranks per-replica meshes decides how many replicas the
budget buys (the chosen :class:`~repro.sharding.plans.ClusterTopology`
is kept on ``cluster.topology`` for reporting).  It places every
replica on its own devices and refuses a budget the host cannot hold.  Every replica is a
full engine with its own KV pool, scheduler, and (optionally) its own
bound TelemetryController from a :class:`ClusterTelemetry`; they share
one clock so cross-replica latency accounting is comparable.

``step`` advances every replica by one engine step, then sweeps
completions into ``router.done``.  Under the frozen-clock sim harness
this is the cluster's tick: the driver advances the shared SimClock by
the MAX of the per-replica step walls (replicas are independent chips
running concurrently — see ``cluster.traffic.serve_trace``).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


class ClusterStalled(RuntimeError):
    """``run_until_done`` exhausted its step budget with requests still
    in flight — a wedged cluster must be LOUD, not indistinguishable
    from a drained one.  Carries the leftover state for the post-mortem."""

    def __init__(self, steps: int, in_flight: int, queued: int,
                 produced: int):
        self.steps = steps
        self.in_flight = in_flight
        self.queued = queued
        self.produced = produced
        super().__init__(
            f"cluster stalled: {in_flight} request(s) in flight "
            f"({queued} queued) after {steps} steps; "
            f"{produced} tokens delivered")


class ServingCluster:
    """Replicas + router; delegates admission/completion to the router."""

    def __init__(self, replicas: List, policy="cost_aware",
                 shed_wait_s: Optional[float] = None,
                 max_reroutes: int = 3, telemetry=None, topology=None):
        from repro.serve.cluster.router import Router
        self.replicas = list(replicas)
        self.router = Router(self.replicas, policy=policy,
                             shed_wait_s=shed_wait_s,
                             max_reroutes=max_reroutes)
        self.telemetry = telemetry
        self.topology = topology
        # optional chaos/fault supervisor (serve.chaos.supervise) — when
        # installed it owns per-replica stepping and the detection sweep
        self.supervisor = None

    # -- construction ---------------------------------------------------------
    @classmethod
    def build(cls, model, params, n_replicas: Optional[int] = None, *,
              policy="cost_aware", clock=None, cost_model=None,
              telemetry=None, shed_wait_s: Optional[float] = None,
              max_reroutes: int = 3, n_devices: Optional[int] = None,
              cell=None, **engine_kwargs) -> "ServingCluster":
        """Stand up a cluster of identical paged replicas, each on its own
        devices.

        Either pass ``n_replicas`` directly (one chip each), or pass a
        device budget (``n_devices``) plus the serving shape (``cell``)
        and the replica count and per-replica mesh are read off
        ``rank_cluster_topologies(...)[0]`` — the cost-model-chosen
        topology.  Replica ``i`` gets the ``i``-th disjoint slice of
        ``jax.devices()`` (``launch.mesh.slice_devices``) as a
        ``('data', 'model')`` mesh, a one-chip replica a ``(1, 1)`` mesh,
        so its params and KV pool live on its own chips.  A budget larger
        than the devices present raises.  ``engine_kwargs`` (max_batch,
        n_blocks, chunk_size, fused, ...) go to every replica verbatim.
        ``telemetry`` may be a :class:`ClusterTelemetry` (one controller
        per replica) — a single TelemetryController cannot be shared,
        its ``bind`` refuses a second engine.

        A simulated cluster (replicas sharing one host device on a
        ``SimClock``) is built from its engines directly:
        ``ServingCluster(replicas, ...)``.
        """
        from repro.launch.mesh import make_host_mesh, slice_devices
        from repro.serve.engine import PagedServingEngine
        topology = None
        if n_replicas is None:
            if n_devices is None or cell is None:
                raise ValueError("build needs n_replicas, or n_devices+cell "
                                 "for the cost model to choose")
            from repro.sharding.plans import rank_cluster_topologies
            topology = rank_cluster_topologies(
                model.cfg, cell, n_devices, cost_model)[0]
            n_replicas = topology.n_replicas
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        per = topology.devices_per_replica if topology is not None else 1
        model_axis = topology.plan.model if topology is not None else 1
        replicas = []
        for i, devs in enumerate(slice_devices(n_replicas, per)):
            controller = telemetry.controller(i) if telemetry else None
            mesh = make_host_mesh(model_axis=model_axis, devices=devs)
            replicas.append(PagedServingEngine(
                model, params, clock=clock, cost_model=cost_model,
                telemetry=controller, mesh=mesh, **engine_kwargs))
        return cls(replicas, policy=policy, shed_wait_s=shed_wait_s,
                   max_reroutes=max_reroutes, telemetry=telemetry,
                   topology=topology)

    # -- admission / completion ----------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               eos_id: Optional[int] = None) -> Optional[int]:
        """Route one request; returns its cluster id, or None if shed."""
        return self.router.submit(prompt, max_new_tokens=max_new_tokens,
                                  eos_id=eos_id)

    @property
    def done(self) -> Dict[int, object]:
        return self.router.done

    @property
    def stats(self):
        return self.router.stats

    # -- failure recovery -----------------------------------------------------
    def replace_replica(self, i: int, engine) -> None:
        """Swap a restarted engine into slot ``i`` on BOTH lists — the
        router copies the replicas list at construction, so the cluster's
        and the router's views must be updated together or they diverge
        on the first warm-rejoin."""
        self.replicas[i] = engine
        self.router.replace_replica(i, engine)

    def _live_replicas(self) -> List:
        """Replicas eligible for work (all of them without a supervisor;
        the router's live set under one — a dead replica's frozen queue
        must not keep ``run_until_done`` spinning)."""
        if self.supervisor is None:
            return self.replicas
        return [self.replicas[j] for j in self.router.live_indices()]

    # -- stepping -------------------------------------------------------------
    def step(self) -> int:
        """One cluster tick: every replica takes one engine step, then
        completions are swept.  Returns total tokens delivered.

        With a chaos supervisor installed, stepping is delegated per
        replica (the supervisor wraps the step with heartbeat + fault
        bookkeeping and skips dead replicas) and the detection/recovery
        sweep runs after the tick."""
        produced = 0
        if self.supervisor is not None:
            for i in range(len(self.replicas)):
                produced += self.supervisor.step_replica(i)
            self.router.collect()
            self.supervisor.after_tick()
        else:
            for eng in self.replicas:
                produced += eng.step()
            self.router.collect()
        return produced

    def run_until_done(self, max_steps: int = 10_000, *,
                       raise_on_stall: bool = True) -> int:
        """Step until every admitted request is collected (or the step
        budget runs out).  Returns total tokens delivered.

        Exhausting ``max_steps`` with requests still in flight raises
        :class:`ClusterStalled` (set ``raise_on_stall=False`` to get the
        old silent return while inspecting the wreckage) — a wedged
        cluster used to return normally, indistinguishable from success.
        """
        produced = 0
        steps = 0
        for _ in range(max_steps):
            if self.router.in_flight == 0 and not any(
                    len(eng.queue) for eng in self._live_replicas()):
                break
            produced += self.step()
            steps += 1
        # flush any one-step-ahead pipelines left in flight
        for eng in self._live_replicas():
            if eng._pending is not None:
                eng._drain(eng._pending)
                eng._pending = None
        self.router.collect()
        if raise_on_stall and self.router.in_flight > 0:
            raise ClusterStalled(
                steps, self.router.in_flight,
                sum(len(eng.queue) for eng in self._live_replicas()),
                produced)
        return produced
