"""Training loop: step function + data pipeline + checkpoint + fault runtime.

Composes the substrates into the production loop:

    restore-or-init -> [train_step -> heartbeat -> watchdog -> ckpt]* -> final

The loop is host-local (each host feeds its DP slice); collectives inside
the jitted step do the cross-host work.  Works identically on the 1-device
CPU test mesh and the 512-chip production mesh.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

import jax

from repro.checkpoint.ckpt import CheckpointManager
from repro.configs.base import ModelConfig, ShapeConfig
from repro.data.pipeline import BatchQueue, DataState, synthetic_lm_producer
from repro.models.model import Model, build_model
from repro.optim import Optimizer, make_optimizer
from repro.runtime.fault import Heartbeat, StepWatchdog
from repro.train.step import make_train_step


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    keep_ckpts: int = 3
    heartbeat_dir: Optional[str] = None
    host_id: int = 0
    n_hosts: int = 1
    seed: int = 0


class Trainer:
    def __init__(self, model: Model, optimizer: Optimizer, mesh,
                 shape: ShapeConfig, tcfg: TrainerConfig, *,
                 producer=None, microbatches: int = 1):
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh
        self.shape = shape
        self.tcfg = tcfg
        self.bundle = make_train_step(model, optimizer, mesh, shape,
                                      microbatches=microbatches)
        self.step_fn = jax.jit(self.bundle.fn,
                               in_shardings=self.bundle.in_shardings,
                               out_shardings=self.bundle.out_shardings,
                               donate_argnums=self.bundle.donate_argnums)
        self.ckpt = CheckpointManager(
            tcfg.ckpt_dir, keep=tcfg.keep_ckpts, host_id=tcfg.host_id,
            n_hosts=tcfg.n_hosts) if tcfg.ckpt_dir else None
        self.hb = Heartbeat(tcfg.heartbeat_dir, tcfg.host_id) \
            if tcfg.heartbeat_dir else None
        self.watchdog = StepWatchdog()
        cfg = model.cfg
        self.producer = producer or synthetic_lm_producer(
            cfg.vocab, shape.seq_len)
        self.history: list = []

    # ------------------------------------------------------------------ run
    def init_state(self):
        params = self.model.init(jax.random.PRNGKey(self.tcfg.seed))
        opt_state = self.optimizer.init(params)
        return params, opt_state

    def run(self) -> Dict[str, Any]:
        tcfg = self.tcfg
        start_step = 0
        data_state = DataState()
        params = opt_state = None

        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            step = self.ckpt.latest_step()
            tmpl_p, tmpl_o = jax.eval_shape(self.init_state)
            (params, opt_state), ds = self.ckpt.restore(
                step, (tmpl_p, tmpl_o),
                (self.bundle.in_shardings[0], self.bundle.in_shardings[1]))
            if ds:
                data_state = DataState.from_dict(ds)
            start_step = step
        if params is None:
            # initialise in place on the mesh: an eager init would build the
            # whole unsharded state on one device before the first step
            params, opt_state = jax.jit(
                self.init_state,
                out_shardings=self.bundle.in_shardings[:2])()

        host_batch = self.shape.global_batch // tcfg.n_hosts
        queue = BatchQueue(self.producer, batch=host_batch,
                           state=data_state)
        try:
            loss = None
            for step in range(start_step, tcfg.steps):
                np_batch, data_state = queue.get()
                batch = {k: jax.numpy.asarray(v) for k, v in np_batch.items()}
                t0 = time.time()
                params, opt_state, metrics = self.step_fn(
                    params, opt_state, batch)
                loss = float(metrics["loss"])
                dt = time.time() - t0
                self.watchdog.record(step, dt, slowest_host=tcfg.host_id)
                if self.hb:
                    self.hb.beat(step)
                if step % tcfg.log_every == 0:
                    self.history.append(
                        {"step": step, "loss": loss, "time_s": dt,
                         "grad_norm": float(metrics["grad_norm"])})
                    print(f"step {step:6d} loss {loss:9.4f} "
                          f"gnorm {float(metrics['grad_norm']):9.3f} "
                          f"{dt*1000:8.1f} ms", flush=True)
                if self.ckpt and step > start_step \
                        and step % tcfg.ckpt_every == 0:
                    self.ckpt.save(step, (params, opt_state),
                                   data_state.as_dict())
            if self.ckpt:
                self.ckpt.save(tcfg.steps, (params, opt_state),
                               data_state.as_dict(), blocking=True)
            return {"params": params, "opt_state": opt_state,
                    "final_loss": loss, "history": self.history,
                    "memory_plan": (self.bundle.memory_plan.report()
                                    if self.bundle.memory_plan else None)}
        finally:
            queue.close()


def quick_train(cfg: ModelConfig, *, steps: int = 20, seq_len: int = 32,
                global_batch: int = 8, ckpt_dir: Optional[str] = None,
                microbatches: int = 1, optimizer: str = "adamw") -> Dict:
    """Single-host convenience wrapper used by examples and tests."""
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh(model=1)
    model = build_model(cfg)
    opt = make_optimizer(optimizer) if optimizer != "sgd" \
        else make_optimizer("sgd")
    shape = ShapeConfig("custom", seq_len, global_batch, "train")
    tcfg = TrainerConfig(steps=steps, ckpt_every=max(steps // 2, 1),
                         ckpt_dir=ckpt_dir, log_every=max(steps // 10, 1))
    trainer = Trainer(model, opt, mesh, shape, tcfg,
                      microbatches=microbatches)
    return trainer.run()
