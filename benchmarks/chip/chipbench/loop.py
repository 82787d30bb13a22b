"""The one generator of training traffic: set-up, measured window, checks.

A traffic file (``traffic/<name>.json``) names the loop and its parameters;
a cell's file (``workloads/<cell>.json``) may override them. Two loops:

``train_save``  the Trainer's own step loop: ``wait_snapshotted()`` before
                each step, and every ``save_every_steps`` steps
                ``block_until_ready(params)`` then ``save()``.
``resume``      a fresh ``Trainer`` resumes the step saved at set-up, and
                its first step runs to ``block_until_ready``; repeated.

Both build the Trainer's parts (its ``CheckpointManager`` through the
Trainer, the jitted ``make_train_step`` with ``donate_argnums=(0,)``), make
the state from the seed in one jitted call, and take the first three steps
at set-up: those are compared with the plain reference after the window.

Every cell runs on the mesh its configuration gives (``"mesh": {"data": d,
"model": m}``, 1 x 1 when absent), laid out as the Trainer lays it out: the
state's shardings are ``Partitioner.train_state_shardings``, the tokens'
``Partitioner.tokens_sharding``.
"""

from __future__ import annotations

import gc
import math
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from . import check, reference
from . import state as S

LOOPS = {}


def loop(name):
    def reg(fn):
        LOOPS[name] = fn
        return fn
    return reg


def now() -> float:
    return time.perf_counter()


def annotate(name: str):
    """A ``bench.*`` host span on the profiler's clock; untraced it costs a
    TraceMe that records nothing."""
    return jax.profiler.TraceAnnotation(f"bench.{name}")


class CompileCount:
    """XLA compilations, counted by JAX's own monitoring events."""

    def __init__(self):
        from jax._src import dispatch
        self.n = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self._event:
            self.n += 1


@dataclass
class Run:
    """Everything a run measured, for the result line and metric readers."""
    cell: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    chips: int = 1                    # the workload's, and the mesh's
    setup_s: float = 0.0
    window_s: float = 0.0
    steps: int = 0
    tokens_per_step: int = 0
    stall_s: float = 0.0
    saves: list = field(default_factory=list)          # SaveMetrics
    resume_s: list = field(default_factory=list)
    restores: list = field(default_factory=list)       # RestoreMetrics
    attempted: int = 0
    failed: int = 0
    window_compiles: int = 0
    memory_peak_bytes: int = 0
    spans: list = field(default_factory=list)          # program TraceEvents
    span_window: tuple = (0.0, 0.0)                    # trace.clock() bounds
    device_trace: object = None                        # devtrace.DeviceTrace
    flops_per_step: float = 0.0                        # the global batch's
    peaks: dict = field(default_factory=dict)
    checks: check.Checks = field(default_factory=check.Checks)
    io: dict = field(default_factory=dict)
    after_s: dict = field(default_factory=dict)   # work after the window
    trace_begin: object = None        # set by a traced run: see run.py
    trace_stop: object = None
    trace_deadline: float = math.inf

    def begin_window(self) -> None:
        if self.trace_begin is not None:
            self.trace_begin()

    def tick(self) -> None:
        """Ends the device trace once its part of the window is over."""
        if self.trace_stop is not None and now() >= self.trace_deadline:
            self.trace_stop()

    def end_window(self) -> None:
        if self.trace_stop is not None:
            self.trace_stop()


class Cell:
    """The Trainer's parts for one cell, built from its files."""

    def __init__(self, run: Run, ckpt_dir: str, step_factory=None):
        from repro.data import DataConfig
        from repro.launch.mesh import make_host_mesh
        from repro.models.config import ModelConfig
        from repro.optim import AdamWConfig
        from repro.sharding.partition import Partitioner
        from repro.train.steps import init_train_state, make_train_step
        from repro.train.trainer import Trainer, TrainerConfig

        cfg, tr = run.config, run.traffic
        m = dict(cfg["model"])
        m["block_pattern"] = tuple(m.get("block_pattern") or ())
        self.model = ModelConfig(**m)
        self.opt = AdamWConfig(**cfg["optimizer"])
        t = cfg["train"]
        self.batch, self.seq_len = t["batch"], t["seq_len"]
        run.tokens_per_step = self.batch * self.seq_len
        self.run = run
        self.seed = run.seed
        self.zipf_a = tr["zipf_a"]
        self.ckpt_dir = ckpt_dir
        shape = cfg.get("mesh", {})
        data, model = int(shape.get("data", 1)), int(shape.get("model", 1))
        if data * model != run.chips:
            raise ValueError(f"mesh data {data} x model {model} is not the "
                             f"{run.chips} chips the cell asks for")
        self.mesh = make_host_mesh(data, model)
        self.devices = list(self.mesh.devices.flat)
        self.tcfg = TrainerConfig(
            steps=0, ckpt_every=max(int(tr.get("save_every_steps", 1)), 1),
            ckpt_dir=ckpt_dir, async_ckpt=True, keep=tr["keep"],
            log_every=0, seed=0)
        self.data_cfg = DataConfig(vocab_size=self.model.vocab_size,
                                   seq_len=self.seq_len + 1,
                                   global_batch=self.batch,
                                   seed=run.seed % (1 << 32))
        self._Trainer = Trainer
        self.trainer = self.new_trainer()
        self.state_shape = jax.eval_shape(
            lambda: init_train_state(jax.random.key(0), self.model))
        part = Partitioner(self.model, self.mesh)
        self.shardings = part.train_state_shardings(self.state_shape)
        self.tokens_sharding = part.tokens_sharding()
        self.template = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            self.state_shape, self.shardings)
        self.key = S.weights_key(run.seed)
        factory = step_factory or make_train_step
        # the state leaves the step in the layout it came in: the compiler
        # would otherwise shard the new params over ``data`` as well
        self.step_fn = jax.jit(factory(self.model, self.opt),
                               donate_argnums=(0,),
                               out_shardings=(self.shardings, None))
        shape = self.state_shape
        self.init_fn = jax.jit(lambda k: S.make_train_state(k, shape),
                               out_shardings=self.shardings)
        self.fp_fn = jax.jit(S.fingerprint)
        b1 = self.opt.b1
        self.grad_fn = jax.jit(lambda mu: S.grad_norms_from_moment(mu, b1))
        self.upd_fn = jax.jit(S.update_norms)

    def reseed(self, seed: int) -> None:
        """Point the cell at another seed's weights and tokens."""
        from dataclasses import replace
        self.run.seed = self.seed = seed
        self.key = S.weights_key(seed)
        self.data_cfg = replace(self.data_cfg, seed=seed % (1 << 32))

    def first_steps(self, trainer, state, n: int):
        """The first ``n`` steps from the seed's state; returns the state,
        the losses and the first step's per-leaf gradient norms."""
        losses, grad_norms = [], None
        for k in range(1, n + 1):
            state, m, _ = self.step(trainer, state, k)
            losses.append(m["loss"])
            if k == 1:
                grad_norms = self.grad_fn(state["opt"]["mu"])
        return state, losses, grad_norms

    def new_trainer(self):
        return self._Trainer(self.model, self.tcfg, mesh=self.mesh,
                             opt_cfg=self.opt, data_cfg=self.data_cfg)

    def batch_at(self, step: int) -> dict:
        b = S.batch_at(self.seed, step, self.batch, self.seq_len,
                       self.model.vocab_size, self.zipf_a)
        return {k: jax.device_put(v, self.tokens_sharding)
                for k, v in b.items()}

    def host_batches(self, n: int) -> list[dict]:
        return [S.batch_at(self.seed, k, self.batch, self.seq_len,
                           self.model.vocab_size, self.zipf_a)
                for k in range(1, n + 1)]

    def save(self, trainer, step: int, state):
        """The Trainer's save barrier: params ready, then ``save()``."""
        jax.block_until_ready(state["params"])
        fp = self.fp_fn(state)
        trainer.pipeline.state.step = step
        t0 = now()
        with annotate("save"):
            sm = trainer.ckpt.save(step, trainer._full_state(state))
        return sm, fp, now() - t0

    def step(self, trainer, state, step: int):
        """One step of the Trainer's loop (its barrier first)."""
        with annotate("feed"):
            b = self.batch_at(step)
        t0 = now()
        with annotate("wait_snapshotted"):
            trainer.ckpt.wait_snapshotted()
        waited = now() - t0
        with annotate("dispatch"):
            state, metrics = self.step_fn(state, b)
        return state, metrics, waited

    def restore_fingerprint(self, step: int) -> np.ndarray:
        restored = self.trainer.ckpt.restore(
            state_template=self.trainer._full_state(self.template),
            step=step)["train"]
        fp = np.asarray(self.fp_fn(restored))
        del restored
        return fp

    def close(self):
        self.trainer.close()


def program_readings(losses, grad_norms, update_norms):
    return reference.Readings([float(x) for x in losses],
                              np.asarray(grad_norms, np.float64),
                              np.asarray(update_norms, np.float64))


def compare_with_reference(run: Run, cell: Cell, prog) -> None:
    """Run the plain reference over the same three steps; add the gaps."""
    t0 = now()
    ref = reference.run_reference(run.config, run.seed,
                                  cell.state_shape["params"],
                                  cell.host_batches(3),
                                  devices=cell.devices)
    run.after_s["reference"] = now() - t0
    gaps = check.training_gaps(prog, ref)
    check.limit_checks(gaps, run.config["limits"], run.checks)
    run.io["gaps"] = {k: gaps[k] for k in check.NUMBERS}
    paths = S.param_paths(cell.state_shape["params"])
    run.io["leaves_left_out"] = [paths[i] for i in gaps["left_out"]]
    run.io["worst_leaves"] = [paths[gaps["grad_worst"]],
                              paths[gaps["update_worst"]]]
    run.io["losses"] = [prog.losses, ref.losses]


# ------------------------------------------------------------ train_save
@loop("train_save")
def train_save(run: Run, cell: Cell, t_start: float, traced) -> None:
    tr = cell.trainer
    every = int(run.traffic["save_every_steps"])   # the cell's to set
    if every < 1:
        raise ValueError(f"save_every_steps must be at least 1, not {every}")
    state, losses, grad_norms = cell.first_steps(
        tr, cell.init_fn(cell.key), 3)
    upd = cell.upd_fn(cell.key, state["params"])
    prog = program_readings(np.asarray(jax.device_get(losses)),
                             np.asarray(grad_norms), np.asarray(upd))
    cell.fp_fn(state)   # compiled here, read at every save of the window
    jax.block_until_ready(state)

    compiles = CompileCount()
    fps = {}
    step, prev = 3, None
    window_losses = []
    with traced(run):
        t0 = now()
        run.setup_s = t0 - t_start
        c0 = compiles.n
        run.begin_window()
        with annotate("window"):
            while now() < t0 + run.seconds:
                run.tick()
                state, m, waited = cell.step(tr, state, step + 1)
                run.stall_s += waited
                step += 1
                run.steps += 1
                if prev is not None:
                    with annotate("sync"):
                        window_losses.append(float(prev))
                prev = m["loss"]
                if step % every == 0:
                    sm, fp, blocked = cell.save(tr, step, state)
                    run.stall_s += blocked
                    run.saves.append(sm)
                    fps[step] = fp
            with annotate("drain"):
                jax.block_until_ready(state)
                window_losses.append(float(prev))
        run.window_s = now() - t0
        run.end_window()
        run.window_compiles = compiles.n - c0
        t1 = now()
        tr.ckpt.wait()
        run.after_s["flush"] = now() - t1
    run.memory_peak_bytes = peak_bytes(cell.devices)
    run.attempted = run.steps
    run.failed = sum(not math.isfinite(x) for x in window_losses)
    del state, m
    gc.collect()

    # the window's last save committed and reads back bit for bit
    committed = set(tr.ckpt.all_steps())
    run.checks.add("saves_missing", int(not fps), 0)
    run.checks.add("last_save_lost", int(bool(fps) and max(fps) not in
                                         committed), 0)
    if fps and max(fps) in committed:
        t1 = now()
        want = np.asarray(fps[max(fps)])
        got = cell.restore_fingerprint(max(fps))
        run.checks.add("restored_leaves_differ",
                       int(np.sum(np.any(want != got, axis=1))), 0)
        run.after_s["restore"] = now() - t1
    compare_with_reference(run, cell, prog)


# ---------------------------------------------------------------- resume
def one_resume(cell: Cell):
    """From a fresh Trainer to its first step done on the device."""
    t0 = now()
    with annotate("resume.trainer"):
        tr = cell.new_trainer()
    try:
        with annotate("resume.restore"):
            restored, start, _ = tr.resume(cell.template)
        fp = cell.fp_fn(restored)
        with annotate("resume.step"):
            state, m, _ = cell.step(tr, restored, start + 1)
            jax.block_until_ready(state)
        dt = now() - t0
        return dt, start, fp, m["loss"], tr.ckpt.last_restore_metrics, state
    finally:
        tr.close()


@loop("resume")
def resume(run: Run, cell: Cell, t_start: float, traced) -> None:
    tr = cell.trainer
    saved = int(run.traffic["saved_step"])
    if saved != 2:
        raise ValueError("the resume loop saves after the second of the "
                         "three checked steps")
    state, losses, grad_norms = cell.first_steps(
        tr, cell.init_fn(cell.key), 2)
    _, fp_saved, _ = cell.save(tr, saved, state)
    tr.ckpt.wait()
    fp_saved = np.asarray(fp_saved)
    del state
    gc.collect()
    # the third checked step runs through a resume: it warms every program
    # the window's resumes use, and its loss is what each of them must give
    dt, start, fp, loss3, _, state = one_resume(cell)
    run.io["warm_resume_s"] = dt
    losses.append(loss3)
    upd = cell.upd_fn(cell.key, state["params"])
    prog = program_readings(np.asarray(jax.device_get(losses)),
                             np.asarray(grad_norms), np.asarray(upd))
    loss3 = np.asarray(loss3)
    warm_ok = start == saved and np.array_equal(np.asarray(fp), fp_saved)
    del state
    gc.collect()

    compiles = CompileCount()
    results = []
    with traced(run):
        t0 = now()
        run.setup_s = t0 - t_start
        c0 = compiles.n
        run.begin_window()
        with annotate("window"):
            while now() < t0 + run.seconds:
                run.tick()
                dt, start, fp, loss, rm, state = one_resume(cell)
                del state
                results.append((start, fp, loss))
                run.resume_s.append(dt)
                run.restores.append(rm)
        run.window_s = now() - t0
        run.end_window()
        run.window_compiles = compiles.n - c0
    run.memory_peak_bytes = peak_bytes(cell.devices)
    gc.collect()

    run.attempted = len(results)
    bad_state = [s != saved or not np.array_equal(np.asarray(f), fp_saved)
                 for s, f, _ in results]
    bad_loss = [np.asarray(l).tobytes() != loss3.tobytes()
                for _, _, l in results]
    run.failed = sum(a or b for a, b in zip(bad_state, bad_loss))
    run.checks.add("resumes_missing", int(not results), 0)
    run.checks.add("restored_states_differ",
                   sum(bad_state) + (not warm_ok), 0)
    run.checks.add("resumed_losses_differ", sum(bad_loss), 0)
    compare_with_reference(run, cell, prog)


def peak_bytes(devices) -> int:
    """The fullest device's peak."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
