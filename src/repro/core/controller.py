"""Live elastic controller: DSP policies driving real JAX training jobs.

This is the *live driver* half of the ``repro.core.tre`` split: an
``ElasticController`` owns execution — building meshes, running optimizer
steps, checkpoint/restore — while every control decision (queue loading,
DR1/DR2 grants, idle-averaged releases, lifecycle transitions) comes from
the very same ``HTCRuntimeEnv`` that the discrete-event emulator drives.
Where the emulator advances a simulated-seconds clock, the controller
advances a ``TickClock``: one control tick = ``steps_per_tick`` optimizer
steps of every running job (the emulator owns wall-clock semantics; the
live controller owns real work).

Per tick, mirroring the emulator's event order (finish events land
strictly before the boundary they precede; scans come last):

  1. tasks that completed last tick are reported via ``env.finish`` —
     freeing their nodes and (through the env's scheduler) chaining queued
     work onto them,
  2. every ``ticks_per_release`` ticks, the env's release check frees
     dynamic blocks covered by the window's time-averaged idle,
  3. the env scans the queue and negotiates node grants with the
     ``ProvisionService`` (1 node = 1 accelerator here; on the production
     pod, 1 node = 8 chips), then first-fit schedules into free devices,
  4. beyond-paper elasticity: a *running* job can be resized into spare
     devices via the env's ``grow``/``shrink`` hooks — the controller
     checkpoints, rebuilds the mesh with a new ``data``-axis extent,
     re-places the state (checkpoints are sharding-agnostic) and resumes;
     injected preemptions are absorbed by restart-from-latest-checkpoint.

Every running job holds its own devices: indices into ``devices`` that no
other running job holds, taken at launch and on ``grow`` and returned on
``shrink`` and at finish. A one-node job runs on its one device (as the
default device of its segment), never on the process default.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import numpy as np

from repro.configs.base import RunConfig
from repro.core.lifecycle import LifecycleService
from repro.core.policy import MgmtPolicy
from repro.core.provision import ProvisionService
from repro.core.tre import HTCRuntimeEnv, TickClock
from repro.data.synthetic import synthetic_batches
from repro.models.lm import LM
from repro.train import checkpoint as ckpt
from repro.train.train_step import build_train_step


@dataclass
class TrainTask:
    """One HTC job: train ``rcfg`` for ``num_steps`` on ``nodes`` devices."""
    name: str
    rcfg: RunConfig
    nodes: int
    num_steps: int
    ckpt_dir: str
    # estimated duration in control ticks (set by the controller at submit;
    # the env records it as a release reservation so backfill scheduling
    # has a profile to work against — restarts make it stale, which the
    # backfill scheduler treats conservatively)
    runtime: float | None = None
    # ---- runtime state ----
    steps_done: int = 0
    devices: list = field(default_factory=list)  # indices it holds
    # one loss per committed step: a preempted segment's losses are
    # rolled back with its steps
    losses: list = field(default_factory=list)
    resizes: int = 0
    restarts: int = 0

    @property
    def done(self) -> bool:
        return self.steps_done >= self.num_steps

    @property
    def alloc(self) -> int:
        """Devices currently assigned."""
        return len(self.devices)


class ElasticController:
    def __init__(self, *, policy: MgmtPolicy, provision: ProvisionService,
                 tre_name: str = "train-tre", devices=None,
                 steps_per_tick: int = 10, ticks_per_release: int = 5,
                 elastic_grow: bool = True,
                 lifecycle: LifecycleService | None = None, scheduler=None):
        self.devices = list(devices if devices is not None else jax.devices())
        self.clock = TickClock()
        self.env = HTCRuntimeEnv(
            tre_name, provision=provision, clock=self.clock,
            launch=self._launch, policy=policy, lifecycle=lifecycle,
            scheduler=scheduler, max_nodes=len(self.devices))
        self.steps_per_tick = steps_per_tick
        self.ticks_per_release = ticks_per_release
        self.elastic_grow = elastic_grow
        self.running: list[TrainTask] = []
        self.finished: list[TrainTask] = []
        self._done_last_tick: list[TrainTask] = []
        self._free_devices = list(range(len(self.devices)))

    # ----------------------------------------------------------- plumbing
    @property
    def name(self) -> str:
        return self.env.name

    @property
    def queue(self) -> list[TrainTask]:
        return self.env.queue

    @property
    def owned(self) -> int:
        return self.env.owned

    @property
    def busy(self) -> int:
        return self.env.busy

    @property
    def free(self) -> int:
        return self.env.free

    @property
    def _tick(self) -> int:
        return int(self.clock.now())

    def submit(self, task: TrainTask) -> None:
        if task.runtime is None:
            task.runtime = math.ceil(
                (task.num_steps - task.steps_done) / self.steps_per_tick)
        self.env.submit(task)

    def _launch(self, task: TrainTask) -> None:
        task.devices = self._take_devices(task.nodes)
        self.running.append(task)

    def _take_devices(self, n: int) -> list[int]:
        # guarded raise, not assert: the env's node count and the free
        # device list must agree; under ``python -O`` a short list would
        # otherwise put two jobs on one device
        if n > len(self._free_devices):
            raise RuntimeError(
                f"mesh wider than device pool: {n} devices wanted, "
                f"{len(self._free_devices)} of {len(self.devices)} free")
        taken = self._free_devices[:n]
        del self._free_devices[:n]
        return taken

    def _return_devices(self, idx: list[int]) -> None:
        self._free_devices = sorted(self._free_devices + idx)

    def devices_of(self, task: TrainTask) -> list:
        """The devices ``task`` holds, in mesh order."""
        return [self.devices[i] for i in task.devices]

    def _mesh_for(self, devices: list):
        if len(devices) <= 1:
            return None
        from jax.sharding import Mesh
        from repro.parallel.sharding import AXIS_DATA
        return Mesh(np.array(devices), (AXIS_DATA,))

    # ------------------------------------------------------------- a tick
    def _run_segment(self, task: TrainTask, fail: bool = False) -> None:
        """Run ``steps_per_tick`` steps of a task on the devices it holds.
        Arrays made here without a placement land on its first device; a
        multi-device job's step spreads them over its mesh."""
        devices = self.devices_of(task)
        mesh = self._mesh_for(devices)
        with jax.default_device(devices[0]):
            lm = LM(task.rcfg.model)
            step_fn, rt, opt = build_train_step(lm, task.rcfg, mesh)
            jit_step = jax.jit(step_fn, donate_argnums=(0,))
            start = ckpt.latest_step(task.ckpt_dir)
            if start is None:
                params = jax.jit(lambda k: lm.init(k)[0])(
                    jax.random.key(task.rcfg.seed))
                state = opt.init(params)
                start = 0
            else:
                abs_state = opt.init_abstract(lm.init(None, abstract=True)[0])
                state, start = ckpt.restore(task.ckpt_dir, abs_state)
            batch_fn = synthetic_batches(task.rcfg, mesh)
            end = min(start + self.steps_per_tick, task.num_steps)
            for step in range(start, end):
                if fail and step == start + 1:
                    task.restarts += 1
                    # simulated preemption: resume from the last checkpoint
                    del task.losses[len(task.losses) - (step - start):]
                    return
                state, metrics = jit_step(state, batch_fn(step))
                task.losses.append(float(metrics["loss"]))
            ckpt.save(task.ckpt_dir, end, state)
            task.steps_done = end

    def tick(self, *, fail_task: str | None = None) -> None:
        """One control cycle: finishes -> release -> scan/schedule -> train."""
        k = int(self.clock.advance())
        # 1) report last tick's completions: frees nodes, chains queued work
        self._flush_done(reschedule=True)
        # 2) window-end release check on time-averaged idle (env integrates
        #    free-node time exactly; the tick is the time unit here)
        if self.ticks_per_release and k % self.ticks_per_release == 0:
            self.env.release_check()
        # 3) DSP scan: negotiate growth, then schedule queued tasks
        self.env.scan()
        # 4) beyond-paper: grow a running job into spare devices (2x max)
        if self.elastic_grow:
            for task in self.running:
                grow = task.alloc
                if self.env.free >= grow and task.alloc < 2 * task.nodes:
                    self.env.grow(task, grow)
                    task.devices += self._take_devices(grow)
                    task.resizes += 1
        # 5) run one segment of every running job
        for task in list(self.running):
            self._run_segment(task, fail=(task.name == fail_task))
            if task.done:
                self.running.remove(task)
                self._done_last_tick.append(task)
        # 6) shrink grown jobs back when the queue needs their devices
        if self.env.queue:
            for task in self.running:
                if task.alloc > task.nodes:
                    self.env.shrink(task, task.alloc - task.nodes)
                    self._return_devices(task.devices[task.nodes:])
                    del task.devices[task.nodes:]
                    task.resizes += 1

    def _flush_done(self, *, reschedule: bool) -> None:
        for task in self._done_last_tick:
            self._return_devices(task.devices)
            task.devices = []
            self.finished.append(task)
            self.env.finish(task, reschedule=reschedule)
        self._done_last_tick.clear()

    def run(self, *, max_ticks: int = 1000, fail_at: dict | None = None) -> None:
        fail_at = dict(fail_at or {})
        while (self.env.queue or self.running or self._done_last_tick) \
                and self._tick < max_ticks:
            self.tick(fail_task=fail_at.pop(self._tick + 1, None))
        # hitting max_ticks must not strand final-tick completions in the
        # deferred list (unreported to the env = phantom busy nodes);
        # reschedule=False so the env doesn't launch queued work into a
        # driver that has stopped ticking
        self._flush_done(reschedule=False)

    def destroy(self) -> None:
        self.env.destroy()
