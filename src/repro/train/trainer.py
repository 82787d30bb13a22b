"""Fault-tolerant training loop with first-class async checkpointing.

Wires together: model step (pjit), synthetic data pipeline, AdamW, and the
paper's checkpoint engine. Capabilities:

  · auto-resume from the latest valid checkpoint (corrupt/partial ones are
    skipped by manifest validity + CRC),
  · async checkpointing — flush overlaps subsequent train steps (the paper's
    stage-3 overlap); blocking time per checkpoint is reported,
  · checkpoint-every-N with versioned GC,
  · data pipeline state rides in the checkpoint (exact-step resume),
  · optional multi-level local→remote flush with hedged stragglers,
  · elastic restore: a run restarted on a different mesh reshards on load.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (CheckpointManager, EngineConfig,
                        MultiLevelCheckpointer, MultiWriterCheckpointer)
from repro.core import trace
from repro.data import DataConfig, SyntheticPipeline
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig
from repro.sharding.partition import Partitioner
from repro.train.steps import init_train_state, make_train_step


@dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_every: int = 0                  # 0 = no checkpointing
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_engine: str = "aggregated"
    async_ckpt: bool = True
    streaming_ckpt: bool = True          # SnapshotPipeline save path
    multilevel_remote: str = ""          # non-empty enables two-level C/R
    ckpt_writers: int = 0                # >1: in-process N-rank concurrent
                                         # writers + rank-0 merge commit
                                         # (DESIGN.md §11)
    keep: int = 3
    log_every: int = 10
    seed: int = 0
    trace: bool = False                  # span tracer on for the whole run
    trace_dir: str = ""                  # Perfetto + .prom exports land here


class Trainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainerConfig,
                 mesh=None, opt_cfg: AdamWConfig | None = None,
                 engine_config: EngineConfig | None = None,
                 data_cfg: DataConfig | None = None):
        self.cfg = cfg
        self.tcfg = tcfg
        self.mesh = mesh
        self.opt_cfg = opt_cfg or AdamWConfig()
        self.data_cfg = data_cfg or DataConfig(
            vocab_size=cfg.vocab_size, seq_len=256, global_batch=8,
            seed=tcfg.seed, frontend_len=cfg.frontend_len,
            frontend_dim=cfg.frontend_dim)
        self.pipeline = SyntheticPipeline(
            self.data_cfg, jax.process_index(), jax.process_count())
        if tcfg.multilevel_remote and tcfg.ckpt_writers > 1:
            raise ValueError(
                "multilevel_remote and ckpt_writers > 1 are mutually "
                "exclusive: the two-level flusher wraps a single manager")
        if tcfg.multilevel_remote:
            self.ckpt = MultiLevelCheckpointer(
                tcfg.ckpt_dir, tcfg.multilevel_remote,
                engine=tcfg.ckpt_engine, config=engine_config,
                async_save=False, keep=tcfg.keep,
                streaming=tcfg.streaming_ckpt)
        elif tcfg.ckpt_every and tcfg.ckpt_writers > 1:
            # N concurrent writer ranks over one directory: the state is
            # row-partitioned per save, every rank flushes its windows, and
            # rank 0 merge-commits the step (restore is elastic: any later
            # run - multi-writer or not - reads the merged manifest)
            self.ckpt = MultiWriterCheckpointer(
                tcfg.ckpt_dir, tcfg.ckpt_writers,
                engine=tcfg.ckpt_engine, config=engine_config,
                async_save=tcfg.async_ckpt, keep=tcfg.keep,
                streaming=tcfg.streaming_ckpt)
        elif tcfg.ckpt_every:
            self.ckpt = CheckpointManager(
                tcfg.ckpt_dir, engine=tcfg.ckpt_engine, config=engine_config,
                async_save=tcfg.async_ckpt, keep=tcfg.keep,
                streaming=tcfg.streaming_ckpt)
        else:
            self.ckpt = None
        self.metrics_log: list[dict] = []

    # ------------------------------------------------------------------ state
    def init_state(self):
        key = jax.random.key(self.tcfg.seed)
        if self.mesh is not None:
            state_shape = jax.eval_shape(
                lambda: init_train_state(key, self.cfg))
            shardings = Partitioner(self.cfg, self.mesh) \
                .train_state_shardings(state_shape)
            with self.mesh:
                state = jax.jit(lambda: init_train_state(key, self.cfg),
                                out_shardings=shardings)()
            return state, shardings
        return init_train_state(key, self.cfg), None

    def _full_state(self, train_state):
        return {"train": train_state, "data": self.pipeline.state_dict()}

    # ------------------------------------------------------------------ run
    def run(self) -> dict:
        if self.tcfg.trace:
            trace.enable()
        try:
            return self._run_traced()
        finally:
            if self.tcfg.trace:
                self._export_trace()
                trace.disable()

    def _export_trace(self) -> None:
        import os
        d = self.tcfg.trace_dir or self.tcfg.ckpt_dir
        os.makedirs(d, exist_ok=True)
        trace.export_perfetto(os.path.join(d, "trace.json"))
        trace.export_prometheus(os.path.join(d, "metrics.prom"))

    def resume(self, state):
        """Restore the latest checkpoint onto ``state``'s placement.

        Returns ``(state, start_step, restore_attr)``: the restored train
        state (or ``state`` itself when there is nothing to resume), the
        step to continue from, and where the resume time went. The data
        pipeline's position is restored with it."""
        latest = self._latest() if self.ckpt is not None else None
        if latest is None:
            return state, 0, {}
        t0 = time.perf_counter()
        restored = self.ckpt.restore(
            state_template=self._full_state(state), step=latest)
        restore_wall = time.perf_counter() - t0
        state = restored["train"]
        self.pipeline.load_state_dict(restored["data"])
        start_step = int(np.asarray(state["step"]))
        # stall attribution: where the resume time went (streaming
        # restores overlap stages, so they no longer sum to wall)
        rm = self.ckpt.last_restore_metrics
        restore_attr = {"restore_seconds": restore_wall}
        if rm is not None:
            restore_attr.update(
                restore_mode=rm.mode,
                restore_read_stall_s=rm.read_stall_seconds,
                restore_decode_s=rm.decode_seconds,
                restore_assemble_s=rm.assemble_seconds,
                restore_h2d_s=rm.h2d_seconds,
                restore_overlap_s=rm.overlap_seconds,
                restore_peak_staged_bytes=rm.peak_staged_bytes,
                restore_direct_bytes=rm.direct_bytes)
        return state, start_step, restore_attr

    def _run_traced(self) -> dict:
        state, shardings = self.init_state()
        step_fn = jax.jit(make_train_step(self.cfg, self.opt_cfg),
                          donate_argnums=(0,))

        state, start_step, restore_attr = self.resume(state)

        ckpt_block_s = 0.0
        ckpt_reported_block_s = 0.0      # sum of SaveMetrics.blocking_seconds
        t_start = time.perf_counter()
        ctx = self.mesh if self.mesh is not None else _nullctx()
        with ctx:
            for step in range(start_step, self.tcfg.steps):
                batch = {k: jnp.asarray(v)
                         for k, v in self.pipeline.batch_at(step).items()}
                if self.ckpt is not None:
                    # step_fn donates the state buffers an in-flight pipelined
                    # save may still be snapshotting — barrier on the staged
                    # snapshot (NOT the flush), and count it as stall time
                    t0 = time.perf_counter()
                    self.ckpt.wait_snapshotted()
                    ckpt_block_s += time.perf_counter() - t0
                state, metrics = step_fn(state, batch)
                self.pipeline.state.step = step + 1
                if self.tcfg.log_every and step % self.tcfg.log_every == 0:
                    m = {k: float(np.asarray(v)) for k, v in metrics.items()}
                    m["step"] = step
                    self.metrics_log.append(m)
                if (self.ckpt is not None and self.tcfg.ckpt_every
                        and (step + 1) % self.tcfg.ckpt_every == 0):
                    jax.block_until_ready(state["params"])
                    t0 = time.perf_counter()
                    sm = self.ckpt.save(step + 1, self._full_state(state))
                    ckpt_block_s += time.perf_counter() - t0
                    ckpt_reported_block_s += sm.blocking_seconds
        jax.block_until_ready(state["step"])
        wall = time.perf_counter() - t_start
        if self.ckpt is not None:
            self.ckpt.wait()
        out = {"state": state, "wall_seconds": wall,
               "ckpt_blocking_seconds": ckpt_block_s,
               "ckpt_blocking_reported_s": ckpt_reported_block_s,
               "metrics": self.metrics_log, **restore_attr}
        if trace.is_enabled():
            rep = trace.stall_report(root="save")
            if rep is not None:
                out["stall_report"] = rep.attribution
                out["stall_wall_seconds"] = rep.wall
        return out

    def _latest(self):
        try:
            if hasattr(self.ckpt, "local"):
                steps = sorted(set(self.ckpt.local.all_steps())
                               | set(self.ckpt._remote_steps()))
                return steps[-1] if steps else None
            return self.ckpt.latest_step()
        except FileNotFoundError:
            return None

    def close(self):
        if self.ckpt is not None:
            self.ckpt.close()


class _nullctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False
