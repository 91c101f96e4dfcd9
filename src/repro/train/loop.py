"""The training loop: data -> step -> metrics/heartbeat -> checkpoint.

Composes every substrate layer: synthetic pipeline (restart-deterministic),
sharded jit step (grad accumulation), async checkpointing, heartbeat-based
fault detection, and straggler flagging.  Used by examples/train_tiny_lm.py
and (with the production mesh) repro.launch.train.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.data.synthetic import DataConfig, Prefetcher, SyntheticLM
from repro.distributed.fault_tolerance import (FaultTolerantRunner,
                                               HeartbeatRegistry)
from repro.launch.mesh import batch_axes, n_batch_shards
from repro.models.zoo import Model
from repro.sharding.plans import train_shardings
from repro.train import optim as optim_mod
from repro.train.step import accum_steps_for, make_train_step


@dataclasses.dataclass
class TrainResult:
    steps_run: int
    final_loss: float
    losses: List[float]
    restored_from: Optional[int]
    events: List
    predicted_step_s: Optional[float] = None   # cost-model verdict
    step_times_s: List[float] = dataclasses.field(default_factory=list)
    # autotuner verdict: kernel -> launch config resolved for this run's
    # shapes (tuned cache entry when present, else the kernel default)
    tuned_configs: Optional[Dict[str, Dict]] = None


def train(model: Model, mesh, *, num_steps: int = 50,
          global_batch: int = 8, seq_len: int = 64,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 25,
          lr: float = 3e-3, seed: int = 0,
          hooks: Optional[List[Callable]] = None,
          cost_model=None, log_prediction: bool = False,
          autotuner=None) -> TrainResult:
    """Run the training loop; with ``cost_model`` (a ``repro.core.costmodel.
    CostModel``) the compiled step is priced once up front and every step's
    metrics carry ``predicted_step_s`` / ``measured_step_s`` so hooks (and
    ``log_prediction=True`` stdout) can track predicted-vs-measured drift —
    the paper's close-the-loop validation applied to a live training run.

    ``autotuner`` (a ``repro.core.autotune.Autotuner``) is installed as the
    process-global tuned-dispatch handle for the duration of the run, so
    the model's ``use_pallas`` kernels trace with the tuned launch configs
    from its cache; the loop also resolves (and, with ``log_prediction``,
    prints) the tuned configs for this run's kernel shapes into
    ``TrainResult.tuned_configs``.  The previous handle is restored on
    exit."""
    from repro.core import autotune as autotune_mod
    prev_tuner = autotune_mod.install(autotuner) \
        if autotuner is not None else None
    try:
        # the context mesh of this run only: activation constraints
        # resolve against it, and it is gone when training returns
        with jax.set_mesh(mesh):
            return _train(model, mesh, num_steps=num_steps,
                          global_batch=global_batch, seq_len=seq_len,
                          ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, lr=lr,
                          seed=seed, hooks=hooks, cost_model=cost_model,
                          log_prediction=log_prediction,
                          autotuner=autotuner)
    finally:
        if autotuner is not None:
            autotune_mod.install(prev_tuner)


def _train_kernel_shapes(cfg, seq_len: int, rows: int) -> Dict[str, Dict]:
    """The tunable-kernel problem shapes one train microstep presents."""
    shapes: Dict[str, Dict] = {}
    if cfg.rwkv:
        shapes["wkv6"] = {
            "batch": rows, "seq": seq_len,
            "heads": cfg.d_model // cfg.rwkv.head_dim,
            "head_dim": cfg.rwkv.head_dim}
    else:
        shapes["flash_attention"] = {
            "batch": rows, "seq_q": seq_len, "seq_kv": seq_len,
            "heads": cfg.padded_heads, "kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim}
    if cfg.ssm:
        shapes["ssm_scan"] = {
            "batch": rows, "seq": seq_len, "d_inner": cfg.d_model,
            "state_dim": cfg.ssm.state_dim}
    return shapes


def _train(model: Model, mesh, *, num_steps, global_batch, seq_len,
           ckpt_dir, ckpt_every, lr, seed, hooks, cost_model,
           log_prediction, autotuner=None) -> TrainResult:
    cfg = model.cfg
    optimizer = optim_mod.make_optimizer(cfg.optimizer, lr_peak=lr)

    # ----- shardings / step ---------------------------------------------------
    from repro.configs.base import ShapeCell
    cell = ShapeCell("loop", "train", seq_len, global_batch)
    psh, osh, bsh, shapes, _ = train_shardings(model, optimizer, mesh, cell)
    accum = accum_steps_for(cfg, global_batch, n_batch_shards(mesh))

    # ----- autotuner: resolve tuned launch configs for this run's shapes ------
    tuned_configs = None
    if autotuner is not None:
        # the jitted step traces GLOBAL microbatch shapes (sharding is a
        # partitioning detail): one accumulation microstep carries
        # global_batch // accum rows
        rows = max(global_batch // accum, 1)
        # key on the model's compute dtype — the same dtype the in-model
        # tuned=True dispatch sees on its activations
        tuned_configs = {
            kernel: autotuner.config_for(kernel, shapes,
                                         dtype=cfg.compute_dtype)
            for kernel, shapes in
            _train_kernel_shapes(cfg, seq_len, rows).items()}
        if log_prediction:
            for kernel, kcfg in tuned_configs.items():
                print(f"autotune: {kernel} -> {kcfg}")

    step_fn = jax.jit(
        make_train_step(model, optimizer, accum, batch_axes(mesh)),
        in_shardings=(psh, osh, bsh), out_shardings=(psh, osh, None),
        donate_argnums=(0, 1))

    # ----- state (fresh or restored) ------------------------------------------
    params = jax.jit(model.init, out_shardings=psh)(jax.random.PRNGKey(seed))
    opt_state = jax.jit(optimizer.init, out_shardings=osh)(params)
    start_step, restored_from = 0, None
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    if mgr is not None:
        got = mgr.restore_latest(like={"p": params, "o": opt_state},
                                 shardings={"p": psh, "o": osh})
        if got is not None:
            start_step, state = got
            params, opt_state = state["p"], state["o"]
            restored_from = start_step

    # ----- data (deterministic resume at start_step) ---------------------------
    data = SyntheticLM(DataConfig(cfg.vocab_size, seq_len, global_batch,
                                  seed=seed))
    def to_dev(b):
        extra = {}
        if cfg.encdec:
            extra["frames"] = jnp.zeros(
                (global_batch, seq_len, cfg.d_model), jnp.bfloat16)
        return {**{k: jnp.asarray(v) for k, v in b.items()}, **extra}
    it = Prefetcher(data.iterate(start_step), transform=to_dev)

    # ----- fault tolerance ------------------------------------------------------
    runner = FaultTolerantRunner(HeartbeatRegistry(["host0"]))

    # ----- cost model: price the compiled step once, log against it each step --
    predicted_step_s = None
    if cost_model is not None:
        peek = next(it)
        # compile ONCE ahead of time, price that executable, and run the
        # loop on it (jit's dispatch cache would not reuse an AOT compile)
        step_fn = step_fn.lower(params, opt_state, peek).compile()
        pred = cost_model.predict_compiled(step_fn.as_text())
        predicted_step_s = pred.step_s
        first_batch = peek
    else:
        first_batch = None

    losses = []
    step_times: List[float] = []
    t_step = time.time()
    for step in range(start_step, num_steps):
        batch = first_batch if first_batch is not None else next(it)
        first_batch = None
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        dt = time.time() - t_step
        t_step = time.time()
        step_times.append(dt)
        runner.on_step("host0", step, dt)
        if predicted_step_s is not None:
            metrics = {**metrics, "predicted_step_s": predicted_step_s,
                       "measured_step_s": dt}
            if log_prediction:
                print(f"step {step}: predicted={predicted_step_s:.3e}s "
                      f"measured={dt:.3e}s "
                      f"ratio={dt / max(predicted_step_s, 1e-12):.2f}x")
        for h in hooks or []:
            h(step, metrics)
        if mgr is not None and (step + 1) % ckpt_every == 0:
            mgr.save(step + 1, {"p": params, "o": opt_state})
    if mgr is not None:
        mgr.save(num_steps, {"p": params, "o": opt_state}, block=True)
        mgr.wait()
    return TrainResult(num_steps - start_step, losses[-1] if losses else
                       float("nan"), losses, restored_from, runner.events,
                       predicted_step_s=predicted_step_s,
                       step_times_s=step_times,
                       tuned_configs=tuned_configs)
