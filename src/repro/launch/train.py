"""End-to-end training driver.

Wires together: config-driven model, optimizer, synthetic data pipeline,
sharded step function, async checkpointing, failure-injection + restart,
straggler monitoring, gradient compression, and the Hemingway adaptive
parallelism controller (observe loss -> refit g(i,m) -> elastic resize).

Usage (CPU example — a ~100M model for a few hundred steps):
  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b --smoke \
      --steps 200 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.compression.gradient import CompressionConfig, GradientCompressor
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import SyntheticTokens
from repro.dist.partitioning import Rules
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import LM
from repro.models.runtime import Runtime
from repro.runtime.failures import FailureInjector, RestartPolicy, SimulatedFailure
from repro.runtime.straggler import StragglerMonitor
from repro.training.optimizers import get_optimizer
from repro.training.trainer import TrainConfig, lr_schedule, make_train_step


@dataclasses.dataclass
class TrainerOptions:
    arch: str = "stablelm-1.6b"
    smoke: bool = True
    steps: int = 100
    seq_len: int = 128
    global_batch: int = 8
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    seed: int = 0
    optimizer: str = "adamw"
    learning_rate: float = 1e-3
    local_steps: int = 1                 # H>1 => local-SGD outer sync
    compression: Optional[str] = None    # int8 | topk | powersgd
    mesh: Optional[Any] = None
    rules: Optional[Rules] = None
    failure_injector: Optional[FailureInjector] = None
    log_every: int = 10


class Trainer:
    """Restartable trainer; `run()` survives SimulatedFailure via restore."""

    def __init__(self, opts: TrainerOptions):
        self.opts = opts
        cfg = (get_smoke_config(opts.arch) if opts.smoke
               else get_config(opts.arch))
        self.cfg = cfg
        rt = Runtime(mesh=opts.mesh, rules=opts.rules,
                     remat="none" if opts.smoke else "full",
                     block_q=64, block_k=64, scan_chunk=32)
        self.lm = LM(cfg, rt)
        self.opt = get_optimizer(opts.optimizer)
        self.tcfg = TrainConfig(learning_rate=opts.learning_rate,
                                warmup_steps=20, total_steps=opts.steps,
                                local_steps=opts.local_steps)
        self.compressor = None
        if opts.compression:
            self.compressor = GradientCompressor(
                CompressionConfig(scheme=opts.compression))
        self.data = SyntheticTokens(
            cfg.vocab_size, opts.seq_len, opts.global_batch, seed=opts.seed,
            n_frontend=cfg.n_frontend_tokens, d_model=cfg.d_model)
        self.ckpt = (CheckpointManager(opts.ckpt_dir)
                     if opts.ckpt_dir else None)
        self.monitor = StragglerMonitor()
        self.history: list = []
        self._build_state()
        self._step_fn = self._make_step()

    # ------------------------------------------------------------------
    def _build_state(self):
        params, axes = self.lm.init(jax.random.PRNGKey(self.opts.seed))
        self.params = params
        self.param_axes = axes
        self.opt_state = self.opt.init(params)
        self.comp_state = (self.compressor.init_state(params)
                           if self.compressor else None)
        self.step = 0

    def _make_step(self):
        base = make_train_step(self.lm, self.opt, self.tcfg)
        return jax.jit(base, donate_argnums=(0, 1))

    # ------------------------------------------------------------------
    def _maybe_restore(self) -> bool:
        if self.ckpt is None:
            return False
        latest = self.ckpt.latest_step()
        if latest is None:
            return False
        tree, meta = self.ckpt.restore(latest)
        self.params = jax.tree.map(jnp.asarray, tree["params"])
        self.opt_state = jax.tree.map(jnp.asarray, tree["opt_state"])
        self.data.load_state_dict(meta["data_state"])
        self.step = int(meta["step"])
        return True

    def _save(self, block: bool = False):
        if self.ckpt is None:
            return
        handle = self.ckpt.save_async(
            self.step,
            {"params": self.params, "opt_state": self.opt_state},
            metadata={"data_state": self.data.state_dict(),
                      "arch": self.cfg.name})
        if block:
            handle.wait()

    # ------------------------------------------------------------------
    def train_some(self, n_steps: int) -> Dict[str, float]:
        last = {}
        for _ in range(n_steps):
            if self.opts.failure_injector is not None:
                self.opts.failure_injector.check(self.step)
            batch_np = self.data.next_batch()
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            t0 = time.perf_counter()
            if self.compressor is not None:
                # compression applied at the sync boundary, outside jit state
                (loss_val, _), grads = jax.value_and_grad(
                    self.lm.loss_fn, has_aux=True)(self.params, batch)
                grads, self.comp_state = self.compressor.compress(
                    grads, self.comp_state)
                from repro.training.optimizers import clip_by_global_norm
                grads, gnorm = clip_by_global_norm(grads, self.tcfg.grad_clip)
                lr = lr_schedule(self.tcfg, jnp.float32(self.step))
                self.params, self.opt_state = self.opt.update(
                    grads, self.opt_state, self.params, lr)
                metrics = {"loss": loss_val, "grad_norm": gnorm}
            else:
                self.params, self.opt_state, metrics = self._step_fn(
                    self.params, self.opt_state, batch, jnp.int32(self.step))
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            self.monitor.observe(self.step, dt)
            last = {k: float(v) for k, v in metrics.items()}
            last["step_time"] = dt
            self.history.append((self.step, last["loss"]))
            if self.opts.log_every and self.step % self.opts.log_every == 0:
                print(f"step {self.step:5d} loss={last['loss']:.4f} "
                      f"({dt*1e3:.0f} ms)", flush=True)
            self.step += 1
            if self.ckpt and self.step % self.opts.ckpt_every == 0:
                self._save()
        return last

    # ------------------------------------------------------------------
    def run(self) -> Dict[str, float]:
        """Train to opts.steps with automatic failure recovery."""
        policy = RestartPolicy()
        self._maybe_restore()
        last: Dict[str, float] = {}
        while self.step < self.opts.steps:
            try:
                last = self.train_some(self.opts.steps - self.step)
            except SimulatedFailure as e:
                if not policy.should_restart():
                    raise
                print(f"[failure] {e}; restoring from checkpoint", flush=True)
                if self.ckpt:
                    self.ckpt.wait()
                if not self._maybe_restore():
                    self._build_state()
                self._step_fn = self._make_step()
        if self.ckpt:
            self._save(block=True)
            self.ckpt.wait()
        return last


# ---------------------------------------------------------------------------
# Chaos mode: the closed elastic loop over the REAL trainer
# ---------------------------------------------------------------------------
class TrainerExecutor:
    """Chaos-loop executor backed by the real LM Trainer.

    Implements the ``repro.runtime.chaos.ChaosLoop`` executor contract with
    the production mechanisms: ``checkpoint``/``restore`` go through the
    CheckpointManager, and ``resize`` rebuilds the trainer at the new
    data-parallel degree and re-places params + optimizer state onto the
    mesh via the elastic re-shard path (repro.runtime.elastic.rescale) from
    the latest checkpoint — the same move a multi-host deployment makes,
    executed here on the debug mesh."""

    def __init__(self, arch: str, m0: int, *, ckpt_dir: str,
                 batch_per_worker: int = 2, seq_len: int = 32,
                 total_steps: int = 200, seed: int = 0):
        from repro.launch.mesh import make_debug_mesh
        self.arch = arch
        self.batch_per_worker = batch_per_worker
        self.seq_len = seq_len
        self.total_steps = total_steps
        self.seed = seed
        self.ckpt_dir = ckpt_dir
        self.mesh = make_debug_mesh(1, 1)
        self.rules = Rules.default(self.mesh)
        self.m0 = m0      # the base TrainConfig lr corresponds to m0's batch
        self.m = 0
        self._build(m0)

    # ------------------------------------------------------------------
    def _opts(self, m: int) -> TrainerOptions:
        return TrainerOptions(
            arch=self.arch, smoke=True, steps=self.total_steps,
            seq_len=self.seq_len, global_batch=m * self.batch_per_worker,
            ckpt_dir=self.ckpt_dir, ckpt_every=10 ** 9,  # loop checkpoints
            seed=self.seed, log_every=0, mesh=self.mesh, rules=self.rules)

    def _build(self, m: int) -> None:
        from repro.training.trainer import rescaled_config
        # every rebuild starts from the BASE config, so the linear-scaling
        # ratio is always m/m0 — per-resize ratios would compound wrongly
        ratio = m / self.m0
        self.trainer = Trainer(self._opts(m))
        if ratio != 1.0:
            self.trainer.tcfg = rescaled_config(self.trainer.tcfg, ratio)
            self.trainer._step_fn = self.trainer._make_step()
        self.m = m

    def _place_from_checkpoint(self) -> None:
        """Host arrays -> sharded arrays on the current mesh (elastic path)."""
        from repro.runtime.elastic import rescale_training_state
        t = self.trainer
        tree, meta = t.ckpt.restore(t.ckpt.latest_step())
        placed = rescale_training_state(tree, self.mesh, self.rules,
                                        t.param_axes, t.opt)
        t.params, t.opt_state = placed["params"], placed["opt_state"]
        t.data.load_state_dict(meta["data_state"])
        t.step = int(meta["step"])

    # -- executor contract ---------------------------------------------
    def outer_step(self, sync_mask=None) -> float:
        metrics = self.trainer.train_some(1)
        return float(metrics["loss"])

    def checkpoint(self) -> None:
        self.trainer._save(block=True)
        self.trainer.ckpt.wait()

    def restore(self) -> None:
        self._place_from_checkpoint()

    def resize(self, m: int) -> None:
        self._build(m)
        self._place_from_checkpoint()

    def relax(self, local_steps: int) -> None:
        from repro.training.trainer import rescaled_config
        self.trainer.tcfg = rescaled_config(self.trainer.tcfg, 1.0,
                                            local_steps=local_steps)
        self.trainer._step_fn = self.trainer._make_step()

    def last_recovery_s(self, op: str) -> Optional[float]:
        """Measured wall-time of the most recent restore/re-shard, read
        from the CheckpointManager's timing log (both ops reduce to the
        same place-shards-from-manifest move, recorded as a restore)."""
        timing = self.trainer.ckpt.last_timing("restore")
        return None if timing is None else float(timing["wall_s"])


def run_chaos_lm(arch: str, trace, ckpt_dir: str, *, m0: int = 1,
                 m_options=(1, 2, 4), seed: int = 0):
    """Closed-loop elastic training of a real (smoke) LM under a chaos
    trace: simulated step times + failures, real losses, real checkpoint
    restores, real mesh re-shards."""
    from repro.core.adaptive import AdaptiveController
    from repro.runtime.chaos import ChaosLoop, ClusterSim, default_system_model
    from repro.telemetry import DriftConfig, StreamingCost

    executor = TrainerExecutor(arch, m0, ckpt_dir=ckpt_dir,
                               total_steps=trace.steps, seed=seed)
    system = default_system_model()
    # objective = train loss; loss > 0 so p_star=0 is a valid gap floor
    controller = AdaptiveController(
        system, target_gap=1.0, p_star=0.0, m_options=m_options,
        refit_every=15, window=80, reshard_cost_s=2.0, min_observations=20)
    # the real trainer reports real restore wall-times (CheckpointManager
    # timings), so the loop charges — and learns — measured recovery costs
    # instead of the assumed constants; ckpt_cost/drift/refit events ride
    # the run log's bus outside rows/signatures
    measured = StreamingCost(
        "recovery:lm", controller.reshard_cost_s,
        DriftConfig(window=8, threshold=0.5, min_points=3, cooldown=8))
    loop = ChaosLoop(ClusterSim(trace), executor, controller,
                     base_compute_s=1.0, d=64, ckpt_every=10,
                     restore_cost_s=3.0, measured_costs=measured)
    log = loop.run()
    log.meta.update(seed=seed, arch=arch, mode="lm")
    return log


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--compression", default=None)
    ap.add_argument("--chaos", default=None, metavar="TRACE.json",
                    help="run the closed-loop elastic trainer under this "
                         "chaos trace (generated with --chaos-seed if the "
                         "file does not exist)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--chaos-out", default=None,
                    help="write the replayable run log JSON here")
    args = ap.parse_args()
    enable_compile_cache()
    if args.chaos is not None:
        import tempfile
        from pathlib import Path

        from repro.runtime.chaos import ChaosTrace
        path = Path(args.chaos)
        if path.exists():
            trace = ChaosTrace.load(path)
        else:
            trace = ChaosTrace.generate(args.chaos_seed, args.steps,
                                        n_hosts=4)
            trace.save(path)
            print(f"[chaos] generated trace -> {path}")
        ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="chaos_ckpt_")
        log = run_chaos_lm(args.arch, trace, ckpt_dir,
                           seed=args.chaos_seed)
        if args.chaos_out:
            log.save(args.chaos_out)
            print(f"[chaos] run log -> {args.chaos_out}")
        print(f"[chaos] steps={len(log.rows)} mitigations="
              f"{log.n_mitigations()} resizes={log.n_resizes()} "
              f"final_m={log.meta['final_m']} "
              f"final_loss={log.meta['final_objective']:.4f}")
        return
    opts = TrainerOptions(arch=args.arch, smoke=args.smoke, steps=args.steps,
                          seq_len=args.seq_len, global_batch=args.global_batch,
                          ckpt_dir=args.ckpt_dir, optimizer=args.optimizer,
                          compression=args.compression)
    trainer = Trainer(opts)
    last = trainer.run()
    print("final:", last)


if __name__ == "__main__":
    main()
