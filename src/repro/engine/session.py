"""TrainSession: the live migration loop (paper Fig. 4b, runnable).

The session owns the training state and implements the :class:`SocJob`
protocol (engine/jobs.py); its old private event loop is now the single-job
special case of :class:`engine.runtime.SwanRuntime` — ``run()`` builds a
one-job runtime, so training standalone and training under multi-job
arbitration execute the exact same code. Per quantum the job:

1. applies device-loss events pushed by the runtime (``on_device_loss``:
   SwanController.force_downgrade + mandatory remesh),
2. executes the active Rung's cached jitted step (``step``),
3. digests the observed latency and lets its SwanController *propose* a
   migration (``observe``) — the runtime arbitrates across co-tenant jobs,
4. applies a committed migration *without restarting* (``migrate``):
   - same-mesh migrations (microbatch / kernel / dtype) carry state over in
     place, casting parameters with launch.steps.cast_params when the dtype
     changes;
   - mesh-shape migrations go through one CheckpointManager save/restore
     round-trip against ElasticController.make_mesh, re-sharding parameters
     under the surviving mesh.

Latency semantics: the wall time of each step is measured; a synthetic
InterferenceTrace (the ``--interference-trace`` flag) multiplies what the
*monitor observes* by the burst's slowdown scaled by the active rung's
interference sensitivity — i.e. downgrading genuinely shrinks the simulated
contention, exactly the relinquish-and-recover dynamic of the paper. A
``latency_fn`` override replaces the observation entirely (deterministic
tests / benchmarks); real compute still runs either way.
"""
from __future__ import annotations

import dataclasses
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint.manager import CheckpointManager, shard_restore
from repro.core.controller import SwanController
from repro.engine.events import InterferenceTrace
from repro.engine.jobs import SocJob, StepReport
from repro.engine.rungs import Rung
from repro.engine.timeline import MigrationRecord, Timeline
from repro.launch.steps import cast_params, init_train_state
from repro.runtime.elastic import ElasticController


@dataclasses.dataclass
class SessionResult:
    losses: List[float]
    timeline: Timeline
    state: Any
    final_rung: str
    controller: Optional[SwanController] = None


class TrainSession(SocJob):
    # background personalization training: a foreground burst pauses it
    preemptible = True

    def __init__(self, cfg, rungs: Sequence[Rung], *, optimizer, batch_fn,
                 lr: float = 0.05, compressor=None,
                 ckpt: Optional[CheckpointManager] = None, ckpt_every: int = 0,
                 elastic: Optional[ElasticController] = None,
                 fault_events: Optional[Callable] = None,
                 trace: Optional[InterferenceTrace] = None,
                 adaptive: bool = True, upgrade_patience: int = 5,
                 latency_fn: Optional[Callable] = None,
                 log_every: int = 0, verbose: bool = True,
                 name: str = "train", priority: float = 1.0):
        if not rungs:
            raise ValueError("need at least one rung")
        if latency_fn is not None and any(
                r.latency_estimate_s is None for r in rungs):
            raise ValueError("latency_fn mode needs latency_estimate_s on "
                             "every rung (observations are compared to them)")
        self.cfg = cfg
        self._rungs = list(rungs)
        self.optimizer = optimizer
        self.batch_fn = batch_fn
        self.lr = lr
        self.compressor = compressor
        self.ckpt = ckpt
        self.ckpt_every = ckpt_every
        self.elastic = elastic
        self.fault_events = fault_events
        self.trace = trace
        self.adaptive = adaptive and len(self._rungs) > 1
        self.latency_fn = latency_fn
        self.log_every = log_every
        self.verbose = verbose
        self.name = name
        self.priority = float(priority)

        n = len(self._rungs)
        profiles = [r.profile(position=i, n=n)
                    for i, r in enumerate(self._rungs)]
        self.ctl = SwanController(profiles, upgrade_patience=upgrade_patience)
        self.controller = self.ctl  # SocJob protocol name (same object)
        self.timeline = Timeline()
        self._expected: Dict[str, float] = {}  # rung name -> clean latency
        if latency_fn is not None:
            for r in self._rungs:
                self._expected[r.name] = r.latency_estimate_s
        self._steps_on_rung = 0
        self._mesh = None
        self._mesh_key = None
        self._migrate_ckpt: Optional[CheckpointManager] = None
        self._migrate_tmpdir = None
        # job binding (set by bind()/run())
        self._until: Optional[int] = None
        self._step_idx = 0
        self._losses: List[float] = []
        self._state = None
        self._init_state = None
        self._rng_seed = 0
        self._prepared = False
        self._last_dt = 0.0
        self._last_rung_name = self.rung.name
        self._ran_tick = None  # last tick whose step() already executed

    # -- rung / mesh plumbing ----------------------------------------------
    def rungs(self) -> Sequence[Rung]:
        return self._rungs

    @property
    def rung(self) -> Rung:
        return self._rungs[self.ctl.idx]

    def _mesh_for(self, rung: Rung):
        if self.elastic is not None:
            shape = None
            if rung.mesh_shape is not None and \
                    int(np.prod(rung.mesh_shape)) <= self.elastic.n_healthy:
                shape = rung.mesh_shape
            return self.elastic.make_mesh(shape=shape)
        if rung.mesh_shape is not None:
            from repro.launch.mesh import make_mesh
            names = ("pod", "data", "model")[-len(rung.mesh_shape):]
            return make_mesh(rung.mesh_shape, names)
        return None

    @staticmethod
    def _mesh_fingerprint(mesh):
        if mesh is None:
            return None
        return (tuple(mesh.devices.shape),
                tuple(d.id for d in mesh.devices.flat))

    def _run_step(self, state, batch):
        fn = self.rung.jitted_step(self.cfg, self.optimizer, lr=self.lr,
                                   compressor=self.compressor)
        if self._mesh is not None:
            with jax.set_mesh(self._mesh):
                return fn(state, batch)
        return fn(state, batch)

    # -- migrations --------------------------------------------------------
    def _ckpt(self) -> CheckpointManager:
        """Manager for migration round-trips: the user's, or a private
        tempdir one (kept separate so an unconfigured session doesn't start
        periodic-checkpointing into a directory nobody reads)."""
        if self.ckpt is not None:
            return self.ckpt
        if self._migrate_ckpt is None:
            # TemporaryDirectory cleans itself up when the session is
            # collected, so migration round-trips don't leak checkpoints
            self._migrate_tmpdir = tempfile.TemporaryDirectory(
                prefix="swan_migrate_")
            self._migrate_ckpt = CheckpointManager(self._migrate_tmpdir.name)
        return self._migrate_ckpt

    def _remesh(self, completed: int, state, new_mesh):
        """One checkpoint round-trip: gather to host under the old mesh,
        re-shard under the new one. ``completed`` is the number of finished
        optimizer steps — a crash-resume from this checkpoint must not skip
        work. Also drops every cached executable — the device set changed
        under them."""
        with obs.get_telemetry().span("train.remesh", job=self.name,
                                      step=completed):
            mgr = self._ckpt()
            mgr.save(completed, state)
            # restore exactly the checkpoint just written — restore_latest
            # could pick up a stale higher-step file in a reused checkpoint
            # directory
            if new_mesh is not None:
                _, state = mgr.restore(completed, mesh=new_mesh)
            else:
                _, state = mgr.restore(completed)
                state = jax.tree_util.tree_map(
                    lambda a: jnp.asarray(a) if hasattr(a, "dtype") else a,
                    state)
            for r in self._rungs:
                r.invalidate()
            self._mesh = new_mesh
            self._mesh_key = self._mesh_fingerprint(new_mesh)
            return state

    def _apply_migration(self, step: int, state, from_rung: Rung,
                         reason: str, completed: int):
        """Carry state from ``from_rung`` onto the (already switched)
        controller's active rung. ``completed`` = optimizer steps finished so
        far (== step before the step runs, step + 1 after). Returns
        (state, MigrationRecord)."""
        to_rung = self.rung
        t0 = time.perf_counter()
        new_mesh = self._mesh_for(to_rung)
        kind = "in-place"
        if self._mesh_fingerprint(new_mesh) != self._mesh_key:
            kind = "remesh"
            state = self._remesh(completed, state, new_mesh)
        if to_rung.param_dtype != from_rung.param_dtype:
            # cast only the parameters: optimizer moments stay float32 (adam
            # keeps full-precision state under bf16 params; recasting them
            # would change the step's input avals and force a recompile)
            state = dict(state)
            state["params"] = cast_params(state["params"], to_rung.dtype)
        cost_s = time.perf_counter() - t0
        expected = self._recalibrate(from_rung, to_rung)
        cost_steps = 0
        if kind == "remesh":
            cost_steps = max(1, int(round(cost_s / expected))) \
                if expected else 1
        rec = self.timeline.record_migration(
            step=step, from_rung=from_rung.name, to_rung=to_rung.name,
            reason=reason, kind=kind, cost_s=round(cost_s, 6),
            cost_steps=cost_steps)
        self._steps_on_rung = 0
        if self.verbose:
            print(f"[swan] step {step}: migrate {from_rung.name} -> "
                  f"{to_rung.name} ({reason}, {kind})")
        return state, rec

    # -- SocJob surface ------------------------------------------------------
    def bind(self, until: int, *, start: int = 0, state=None,
             rng_seed: int = 0) -> "TrainSession":
        """Set this job's work target before handing it to a SwanRuntime.
        ``run()`` does this implicitly for the standalone path."""
        self._until = until
        self._step_idx = start
        self._losses = []
        self._init_state = state
        self._rng_seed = rng_seed
        self._prepared = False
        return self

    @property
    def done(self) -> bool:
        return self._prepared and self._step_idx >= self._until

    def _materialize(self, state):
        """Align a host/checkpoint state with the active rung and place it on
        the current mesh. A checkpoint may have been written on any rung
        (e.g. the bf16 bottom), so the parameter dtype is re-aligned here."""
        state = dict(state)
        state["params"] = cast_params(state["params"], self.rung.dtype)
        if self._mesh is not None:
            host = jax.tree_util.tree_map(
                lambda a: jax.device_get(a) if hasattr(a, "dtype") else a,
                state)
            return shard_restore(host, self._mesh)
        return jax.tree_util.tree_map(
            lambda a: jnp.asarray(a) if hasattr(a, "dtype") else a, state)

    def prepare(self) -> None:
        if self._prepared:
            return
        if self._until is None:
            raise RuntimeError("TrainSession must be bind()-ed (or run via "
                               "run()) before a runtime can step it")
        self._mesh = self._mesh_for(self.rung)
        self._mesh_key = self._mesh_fingerprint(self._mesh)
        state = self._init_state
        if state is None:
            model = self.rung.build_model(self.cfg)
            state = init_train_state(model, self.optimizer,
                                     jax.random.PRNGKey(self._rng_seed),
                                     compressor=self.compressor)
        self._state = self._materialize(state)
        self._prepared = True

    # -- preemption (foreground bursts) --------------------------------------
    def on_pause(self, tick: int) -> None:
        """Checkpoint and *release* the training state — the foreground app
        that preempted us wants the memory. The checkpoint is labeled with
        the completed-step count, so resume (or a crash during the pause)
        restarts exactly at the pre-pause step."""
        if not self._prepared or self._state is None:
            return
        t0 = time.perf_counter()
        self._ckpt().save(self._step_idx, self._state)
        self._state = None
        self.timeline.record_migration(
            step=self._step_idx, from_rung=self.rung.name,
            to_rung=self.rung.name, reason="pause", kind="pause",
            cost_s=round(time.perf_counter() - t0, 6))

    def on_resume(self, tick: int) -> None:
        """Reload the pause checkpoint through the normal restore machinery.
        ``restore_latest`` skips a corrupt/torn newest file (chaos: crash
        mid-write) and falls back to the previous step — in that case the
        step counter rewinds with the state so no optimizer step is skipped;
        in the normal case the restored step IS the pre-pause step."""
        if not self._prepared or self._state is not None:
            return
        t0 = time.perf_counter()
        restored = self._ckpt().restore_latest()
        if restored is None:
            raise RuntimeError(
                f"{self.name}: no readable checkpoint to resume from")
        step, state = restored
        self._state = self._materialize(state)
        self._step_idx = int(step)
        self._steps_on_rung = 0  # first post-resume step re-warms caches
        self.timeline.record_migration(
            step=self._step_idx, from_rung=self.rung.name,
            to_rung=self.rung.name, reason="resume", kind="pause",
            cost_s=round(time.perf_counter() - t0, 6))

    def on_device_loss(self, tick: int, failed: Sequence[int]) -> None:
        """Device loss forces a downgrade + remesh (the runtime already
        marked the shared pool)."""
        if self.elastic is None:
            return
        step = self._step_idx
        prev = self.ctl.idx
        self.ctl.force_downgrade("device-loss")
        if self.ctl.idx != prev:
            # the step hasn't run yet: only `step` steps finished
            self._state, _ = self._apply_migration(
                step, self._state, self._rungs[prev], "device-loss",
                completed=step)
        new_mesh = self._mesh_for(self.rung)
        if self._mesh_fingerprint(new_mesh) != self._mesh_key:
            # no rung change (ladder bottom) but a lost device may hold
            # shards: remesh is still mandatory
            t0 = time.perf_counter()
            self._state = self._remesh(step, self._state, new_mesh)
            self.timeline.record_migration(
                step=step, from_rung=self.rung.name, to_rung=self.rung.name,
                reason="device-loss", kind="remesh",
                cost_s=round(time.perf_counter() - t0, 6), cost_steps=1)
            self._steps_on_rung = 0

    def step(self, tick: int) -> StepReport:
        step = self._step_idx
        rung = self.rung
        self._ran_tick = tick
        tel = obs.get_telemetry()
        with tel.span("train.batch", job=self.name, step=step):
            batch = self.batch_fn(step)
        warmup = self._steps_on_rung == 0
        # compile=True marks the first quantum on a rung (pays trace+compile)
        # so the trace distinguishes compile spans from steady-state steps
        with tel.span("train.step", job=self.name, step=step,
                      rung=rung.name, compile=warmup):
            t0 = time.perf_counter()
            with tel.span("train.dispatch"):
                self._state, metrics = self._run_step(self._state, batch)
            with tel.span("train.wait"):
                loss = float(metrics["loss"])  # blocks until the step is done
            dt = time.perf_counter() - t0
        self._steps_on_rung += 1
        self._losses.append(loss)
        self._last_dt = dt
        self._last_rung_name = rung.name
        leaves = jax.tree_util.tree_leaves(batch)
        work = float(leaves[0].shape[0]) if leaves else 1.0  # samples
        return StepReport(latency_s=dt, work=work, loss=loss, warmup=warmup)

    def observe(self, tick: int, report: StepReport,
                slowdown: float) -> Optional[str]:
        step = self._step_idx
        rung = self.rung
        dt = report.latency_s
        # what the monitor sees
        if self.latency_fn is not None:
            observed = float(self.latency_fn(step, rung, dt))
        else:
            observed = dt * slowdown
        report.observed_s = observed
        self.timeline.record_step(step=step, rung=rung.name,
                                  latency_s=round(dt, 6),
                                  observed_s=round(observed, 6),
                                  loss=report.loss, warmup=report.warmup,
                                  work=report.work)
        return self._monitor_proposal(report, rung, dt, observed)

    def migrate(self, direction: str, reason: str,
                tick: int) -> Optional[MigrationRecord]:
        prev = self.ctl.idx
        self.ctl.commit(direction, reason)
        if self.ctl.idx == prev:
            return None
        # post-observation migrations land after this tick's step (step + 1
        # finished); a pre-step commit (the runtime's energy walk-down) must
        # not label the remesh checkpoint with work that hasn't happened
        ran = self._ran_tick == tick
        self._state, rec = self._apply_migration(
            self._step_idx, self._state, self._rungs[prev], reason,
            completed=self._step_idx + (1 if ran else 0))
        return rec

    def end_tick(self, tick: int) -> None:
        step = self._step_idx
        if self.log_every and (step % self.log_every == 0
                               or step == self._until - 1):
            print(f"step {step:5d} loss {self._losses[-1]:8.4f} "
                  f"({self._last_dt * 1e3:.0f} ms) [{self._last_rung_name}]")
        if self.ckpt is not None and self.ckpt_every and \
                (step + 1) % self.ckpt_every == 0:
            self.ckpt.save(step + 1, self._state)
        self._step_idx = step + 1

    def publish_metrics(self, metrics) -> None:
        if self._losses:
            metrics.gauge("train_loss").labels(job=self.name).set(
                self._losses[-1])
        metrics.gauge("train_steps_total").labels(job=self.name).set(
            float(self._step_idx))

    def finalize(self) -> None:
        if self.ckpt is not None and self._losses:
            self.ckpt.save(self._step_idx, self._state)

    def result(self) -> SessionResult:
        return SessionResult(losses=self._losses, timeline=self.timeline,
                             state=self._state, final_rung=self.rung.name,
                             controller=self.ctl)

    # -- standalone entry point ---------------------------------------------
    def run(self, steps: int, *, start: int = 0, state=None,
            rng_seed: int = 0) -> SessionResult:
        """Train standalone: a single-job SwanRuntime over this session.
        The loop structure is the old event loop's; the one behavioral
        change riding along is the controller's post-migration sample skip
        (migrate -> no bounce), which can shift migration steps by one
        versus pre-SocRuntime timelines."""
        from repro.engine.runtime import SwanRuntime
        self.bind(steps, start=start, state=state, rng_seed=rng_seed)
        rt = SwanRuntime([self], trace=self.trace, elastic=self.elastic,
                         fault_events=self.fault_events)
        rt.run(steps, start=start)
        return self.result()
