#!/usr/bin/env python3
"""Smoke run of the checkpointed trainer and its Pallas kernels on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one host with four chips (2x2)

One process drives the chip(s). Each phase prints one line: its wall time,
the XLA compile time and persistent-cache hits inside it, what ran and what
it checked. A failed check raises, so the run exits non-zero. Only when
every phase passed on a TPU is the last line printed:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Phases on one chip:
  device   devices, versions, compile cache, free disk, I/O backend, and
           whether O_DIRECT holds in the checkpoint directory
  kernels  quantize/dequantize, fp128 digests and fused quantize+digest on
           a 64 MiB fp32 tensor at the 256 KiB delta chunk, bit for bit
           against kernels/ref.py and the numpy host twins, with the Pallas
           custom call shown in each compiled program
  trainer  xlstm-350m at its published widths through launch/train.py: 4
           steps with async saves every 2 steps, then a fresh Trainer
           restores step 4 bit for bit onto the chip and takes step 5
  delta    delta saves of the device-resident params around one step,
           restored bit for bit; an int8 save of one AdamW moment tree,
           restored equal to the host twin's dequantized values

With --four-chips only this phase runs:
  four_chips  xlstm-350m on a 2x2 mesh, 2 steps and one save, restored
           onto a 4x1 mesh and onto one device, both bit for bit, with
           every leaf's shards on 4 distinct devices

Checkpoints go to ``.chip_smoke_ckpt/`` in the checkout and are deleted at
the end.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import mmap
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import numpy as np  # noqa: E402

CKPT_ROOT = os.path.join(REPO, ".chip_smoke_ckpt")
FULL_MODEL = ("--full", "--arch", "xlstm-350m")
KERNEL_BYTES = 64 << 20


class CheckFailed(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def same_bits(a, b) -> bool:
    """Bitwise equality of two arrays (NaN-safe, dtype-exact)."""
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and np.array_equal(np.ascontiguousarray(a).reshape(-1)
                               .view(np.uint8),
                               np.ascontiguousarray(b).reshape(-1)
                               .view(np.uint8)))


def check_same(a, b, what: str) -> None:
    """check() that two arrays are bitwise equal, saying how they differ."""
    if same_bits(a, b):
        return
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype != b.dtype or a.shape != b.shape:
        raise CheckFailed(f"{what}: {a.dtype}{a.shape} vs {b.dtype}{b.shape}")
    bad = np.flatnonzero(a.reshape(-1).view(np.uint8).reshape(a.size, -1)
                         != b.reshape(-1).view(np.uint8).reshape(b.size, -1)
                         ) if a.size else np.zeros(0, int)
    idx = np.unique(bad // max(a.itemsize, 1))[:4]
    raise CheckFailed(
        f"{what}: {len(np.unique(bad // max(a.itemsize, 1)))} of {a.size} "
        f"elements differ, e.g. at {idx.tolist()}: "
        f"{a.reshape(-1)[idx].tolist()} vs {b.reshape(-1)[idx].tolist()}")


def same_tree(a, b) -> list[str]:
    """Paths of leaves that differ bitwise (empty when the trees match)."""
    import jax
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_flatten_with_path(b)[0]
    if [p for p, _ in fa] != [p for p, _ in fb]:
        return ["<tree structure>"]
    return [jax.tree_util.keystr(p) for (p, x), (_, y) in zip(fa, fb)
            if not same_bits(x, y)]


# ------------------------------------------------------------- host twins
def quantize_host(x: np.ndarray):
    """numpy twin of kernels.quantize.quant_rows: (R, C) f32 ->
    (int8 q (R, C), f32 scales (R,))."""
    x = np.asarray(x, np.float32)
    absmax = np.max(np.abs(x), axis=1, keepdims=True)
    scale = np.where(absmax > 0, absmax * np.float32(1.0 / 127.0),
                     np.float32(1.0)).astype(np.float32)
    q = np.clip(np.round(x / scale), -127, 127).astype(np.int8)
    return q, scale.reshape(-1)


def dequantize_host(q: np.ndarray, s: np.ndarray) -> np.ndarray:
    return q.astype(np.float32) * s.astype(np.float32)[:, None]


def int8_roundtrip_host(leaf: np.ndarray) -> np.ndarray:
    """What an int8 save + restore of ``leaf`` must give back."""
    from repro.core import quant_codec
    flat = np.asarray(leaf, np.float32).reshape(-1)
    rows = quant_codec.packed_rows(flat.size)
    padded = np.zeros(rows * quant_codec.GROUP_COLS, np.float32)
    padded[:flat.size] = flat
    q, s = quantize_host(padded.reshape(rows, quant_codec.GROUP_COLS))
    return dequantize_host(q, s).reshape(-1)[:flat.size] \
        .astype(leaf.dtype).reshape(leaf.shape)


# ------------------------------------------------------------------ probes
def o_direct_holds(directory: str) -> bool:
    """Whether an aligned O_DIRECT write succeeds in ``directory``."""
    path = os.path.join(directory, ".o_direct_probe")
    buf = mmap.mmap(-1, mmap.PAGESIZE)        # page-aligned
    try:
        fd = os.open(path, os.O_CREAT | os.O_WRONLY | os.O_DIRECT, 0o644)
    except OSError:
        return False
    try:
        return os.write(fd, buf) == mmap.PAGESIZE
    except OSError:
        return False
    finally:
        os.close(fd)
        os.unlink(path)
        buf.close()


def package_version(name: str) -> str:
    try:
        return importlib.metadata.version(name)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


class CompileClock:
    """XLA compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.seconds = 0.0
        self.cache_hits = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == self._event:
            self.seconds += duration

    def _count(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reading(self) -> tuple[float, int]:
        return self.seconds, self.cache_hits


def run_phase(name: str, clock: CompileClock | None, fn, *args, **kw):
    c0 = clock.reading() if clock else (0.0, 0)
    t0 = time.perf_counter()
    info, result = fn(*args, **kw)
    wall = time.perf_counter() - t0
    line = {"wall_s": wall}
    if clock:
        c1 = clock.reading()
        line.update(compile_s=c1[0] - c0[0], cache_hits=c1[1] - c0[1])
    line.update(info)
    print(f"phase {name}: {json.dumps(line, default=str)}", flush=True)
    return result


# ------------------------------------------------------------------ phases
def phase_device(ckpt_root: str):
    import jax
    import jaxlib

    from repro.core.io_engine import resolve_backend
    devs = jax.devices()
    os.makedirs(ckpt_root, exist_ok=True)
    info = {
        "devices": [str(d) for d in devs],
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": package_version("libtpu"),
        "compile_cache": jax.config.jax_compilation_cache_dir,
        "disk_free_gib": shutil.disk_usage(ckpt_root).free / 2 ** 30,
        "io_backend": resolve_backend("auto"),
        "o_direct": o_direct_holds(ckpt_root),
    }
    return info, None


def phase_kernels(nbytes: int = KERNEL_BYTES, chunk_bytes: int | None = None,
                  interpret: bool = False, seed: int = 0):
    """Every main-path kernel at ``nbytes`` of fp32, bit for bit against
    ref.py and the host twins. ``interpret`` runs the Pallas kernels in the
    interpreter (CPU rehearsal); the library dispatchers then take their
    XLA oracles, which must agree all the same."""
    import jax
    import jax.numpy as jnp

    from repro.core import trace
    from repro.core.delta import DEFAULT_CHUNK_BYTES
    from repro.kernels import fingerprint as fpk
    from repro.kernels import ref
    from repro.kernels.quantize import (LANE_COLS, dequantize_blocks,
                                        quantize_blocks)
    cb = chunk_bytes or DEFAULT_CHUNK_BYTES
    rows = nbytes // 4 // LANE_COLS
    x = jax.random.normal(jax.random.key(seed), (rows, LANE_COLS),
                          jnp.float32)
    xh = np.asarray(x)
    qh, sh = quantize_host(xh)

    q, s = quantize_blocks(x, interpret=interpret)
    qr, sr = jax.jit(ref.quantize_blocks_ref)(x)
    for name, a, b in (("q~ref", q, qr), ("q~host", q, qh),
                       ("s~ref", s, sr), ("s~host", s, sh)):
        check_same(a, b, f"quantize_blocks {name}")
    d = dequantize_blocks(q, s, out_dtype=jnp.float32, interpret=interpret)
    dr = jax.jit(ref.dequantize_blocks_ref, static_argnums=2)(
        q, s, jnp.float32)
    check_same(d, dr, "dequantize_blocks ~ ref")
    check_same(d, dequantize_host(qh, sh), "dequantize_blocks ~ host")

    # fp128 digests of the tensor's byte image
    flat = x.reshape(-1)
    lanes, lens = fpk._fp_prep_jit(flat, cb)
    dig_host = fpk.fingerprint_chunks_host(xh.reshape(-1).view(np.uint8), cb)
    for name, got in (
            ("fingerprint_digests", fpk.fingerprint_digests(flat, cb)),
            ("fingerprint_chunks", fpk.fingerprint_chunks(
                lanes, lens, interpret=interpret)),
            ("ref", jax.jit(ref.fingerprint_chunks_ref)(lanes, lens))):
        check_same(got, dig_host, f"fp128 {name} ~ host")

    # fused quantize + digest of the packed qs-stream: whole chunks, then a
    # ragged tensor whose q tail and scales take the tail path
    trace.enable()
    try:
        for r in (rows, rows - 8):
            qf, sf, df = fpk.quant_fingerprint(x[:r], cb)
            check_same(qf, qh[:r], f"quant_fingerprint q ~ host ({r} rows)")
            check_same(sf, sh[:r], f"quant_fingerprint s ~ host ({r} rows)")
            stream = np.concatenate([qh[:r].reshape(-1).view(np.uint8),
                                     sh[:r].view(np.uint8)])
            check_same(df, fpk.fingerprint_chunks_host(stream, cb),
                       f"quant_fingerprint digests ~ host ({r} rows)")
        counts = trace.active().counters()
    finally:
        trace.disable()
    body = rows * LANE_COLS // cb * (cb // LANE_COLS)
    qk, sk, dk = fpk.quantize_fingerprint_blocks(x[:body], cb,
                                                 interpret=interpret)
    _, _, dref = jax.jit(ref.quantize_fingerprint_blocks_ref,
                         static_argnums=1)(x[:body], cb)
    check_same(qk, qh[:body], "quantize_fingerprint_blocks q ~ host")
    check_same(sk, sh[:body], "quantize_fingerprint_blocks s ~ host")
    check_same(dk, dref, "quantize_fingerprint_blocks digests ~ ref")
    check_same(dk, fpk.fingerprint_chunks_host(
        qh[:body].reshape(-1).view(np.uint8), cb),
        "quantize_fingerprint_blocks digests ~ host")

    info = {"tensor_bytes": int(xh.nbytes), "chunk_bytes": cb,
            "digest_chunks": int(dig_host.shape[0]),
            "quant_fingerprint": counts, "bit_identical": True}
    if not interpret:
        hlo = {
            "quantize_blocks": jax.jit(quantize_blocks).lower(x),
            "dequantize_blocks": jax.jit(dequantize_blocks).lower(q, s),
            "fingerprint_chunks": jax.jit(fpk.fingerprint_chunks)
            .lower(lanes, lens),
            "quantize_fingerprint_blocks": jax.jit(
                fpk.quantize_fingerprint_blocks, static_argnums=1)
            .lower(x[:body], cb),
        }
        for name, lowered in hlo.items():
            check("tpu_custom_call" in lowered.compile().as_text(),
                  f"{name}: no Pallas custom call in the compiled HLO")
        check(counts.get("quant_fingerprint.kernel") == 2
              and "quant_fingerprint.oracle" not in counts,
              f"quant_fingerprint took the oracle: {counts}")
        info["tpu_custom_call"] = sorted(hlo)
    return info, None


def train_argv(model_args, ckpt_dir: str, steps: int, every: int,
               *extra: str) -> list[str]:
    return [*model_args, "--steps", str(steps), "--ckpt-every", str(every),
            "--ckpt-dir", ckpt_dir, "--keep", "2", "--log-every", "1",
            *extra]


def _losses(out) -> list[float]:
    return [m["loss"] for m in out["metrics"]]


def phase_trainer(ckpt_dir: str, model_args=FULL_MODEL, steps: int = 4,
                  every: int = 2):
    """Train with async saves every ``every`` steps, then resume in a fresh
    Trainer: bit-exact restore onto the device, one more finite step."""
    import jax

    from repro.launch.train import build_trainer, parse_args
    platform = jax.devices()[0].platform
    t1 = build_trainer(parse_args(train_argv(model_args, ckpt_dir, steps,
                                             every)))
    try:
        out1 = t1.run()
        committed = t1.ckpt.all_steps()
    finally:
        t1.close()
    losses = _losses(out1)
    check(len(losses) == steps and bool(np.all(np.isfinite(losses))),
          f"first run losses {losses}")
    check(committed == list(range(every, steps + 1, every))[-2:],
          f"committed steps {committed}")
    saved = jax.device_get(out1["state"])
    info = {
        "arch": t1.cfg.name, "layers": t1.cfg.num_layers,
        "d_model": t1.cfg.d_model, "vocab": t1.cfg.vocab_size,
        "params": sum(int(np.prod(leaf.shape)) for leaf in
                      jax.tree_util.tree_leaves(saved["params"])),
        "state_bytes": sum(int(np.asarray(leaf).nbytes) for leaf in
                           jax.tree_util.tree_leaves(saved)),
        "batch": t1.data_cfg.global_batch, "seq_len": t1.data_cfg.seq_len,
        "steps": steps, "async_saves": steps // every,
        "committed_steps": committed, "losses": losses,
        "run_wall_s": out1["wall_seconds"],
        "ckpt_blocking_s": out1["ckpt_blocking_seconds"],
    }
    del out1

    t2 = build_trainer(parse_args(train_argv(model_args, ckpt_dir,
                                             steps + 1, every)))
    try:
        fresh, _ = t2.init_state()
        t0 = time.perf_counter()
        restored, start, _ = t2.resume(fresh)
        jax.block_until_ready(restored)
        info["restore_s"] = time.perf_counter() - t0
        del fresh
        check(start == steps, f"resumed from step {start}, not {steps}")
        check(all(isinstance(leaf, jax.Array)
                  and {d.platform for d in leaf.devices()} == {platform}
                  for leaf in jax.tree_util.tree_leaves(restored)),
              f"restored leaves are not all jax.Arrays on the {platform}")
        diff = same_tree(jax.device_get(restored), saved)
        check(not diff, f"restored state differs from the saved one: "
                        f"{diff[:5]}")
        del restored
        out2 = t2.run()
    finally:
        t2.close()
    losses2 = _losses(out2)
    check(len(losses2) == 1 and bool(np.isfinite(losses2[0])),
          f"resumed run losses {losses2}")
    check(int(np.asarray(out2["state"]["step"])) == steps + 1,
          "resumed run did not take its step")
    info.update(restored_bit_exact=True, leaves_on=platform,
                resumed_from=start, resumed_run_wall_s=out2["wall_seconds"],
                resumed_restore_s=out2.get("restore_seconds"),
                resumed_loss=losses2[0])
    return info, (t2, out2["state"])


def phase_delta(ckpt_root: str, trainer, state):
    """Delta saves of the trainer's device-resident params around one step,
    then an int8 delta save of one AdamW moment tree."""
    import jax

    from repro.core import CheckpointManager, trace
    from repro.core.delta import DEFAULT_CHUNK_BYTES
    from repro.core.manifest import Manifest
    from repro.core.quant_codec import packed_rows
    from repro.core.serialization import path_str
    from repro.kernels.fingerprint import fused_kernel_fits
    from repro.train.steps import make_train_step
    step_fn = jax.jit(make_train_step(trainer.cfg, trainer.opt_cfg),
                      donate_argnums=(0,))
    batch = {k: jax.numpy.asarray(v) for k, v in trainer.pipeline.batch_at(
        int(np.asarray(state["step"]))).items()}
    d = os.path.join(ckpt_root, "delta")
    with CheckpointManager(d, delta=True, keep=None) as mgr:
        m0 = mgr.save(0, {"params": state["params"]})
        state, _ = step_fn(state, batch)         # donates the saved params
        params = state["params"]
        m1 = mgr.save(1, {"params": params})
        got = mgr.restore(state_template={"params": params}, step=1)
    diff = same_tree(jax.device_get(got["params"]), jax.device_get(params))
    check(not diff, f"delta restore differs at {diff[:5]}")
    check(m1.chunks_total == m0.chunks_total > 0, "delta chunk grids differ")
    check(m0.d2h_bytes > 0 and m1.d2h_bytes > 0, "no D2H bytes counted")
    del got
    info = {"params_bytes": sum(int(leaf.nbytes) for leaf in
                                jax.tree_util.tree_leaves(params))}
    for name, m in (("save_0", m0), ("save_1", m1)):
        info[name] = {"chunks_dirty": m.chunks_dirty,
                      "chunks_total": m.chunks_total,
                      "written_bytes": m.written_bytes,
                      "d2h_bytes": m.d2h_bytes,
                      "blocking_s": m.blocking_seconds}
    info["delta_restore_bit_exact"] = True

    mu = state["opt"]["mu"]
    dq = os.path.join(ckpt_root, "int8")
    trace.enable()
    try:
        with CheckpointManager(dq, delta=True, keep=None,
                               quantize_prefixes=("mu/",)) as mgr:
            mq = mgr.save(0, {"mu": mu})
            counts = trace.active().counters()
            got = mgr.restore(state_template={"mu": mu}, step=0)
    finally:
        trace.disable()
    quantized = set(Manifest.load(os.path.join(dq, "step_00000000"))
                    .extra.get("quantized", ()))
    check(bool(quantized), "no moment leaf was quantized")
    def by_key(tree):
        flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
        return {path_str(p): leaf for p, leaf in flat}
    host, got = by_key({"mu": mu}), by_key(got)
    bad = [k for k, leaf in host.items()
           if not same_bits(got[k], int8_roundtrip_host(leaf)
                            if k in quantized else leaf)]
    check(not bad, f"int8 restore differs from the host twin at {bad[:5]}")
    if jax.devices()[0].platform == "tpu":
        fits = sum(fused_kernel_fits(packed_rows(host[k].size),
                                     DEFAULT_CHUNK_BYTES) for k in quantized)
        check(counts.get("quant_fingerprint.kernel", 0) == fits,
              f"quant_fingerprint kernel ran {counts} for {fits} leaves")
    info["int8"] = {"moment_bytes": sum(int(leaf.nbytes)
                                        for leaf in host.values()),
                    "quantized_leaves": len(quantized),
                    "written_bytes": mq.written_bytes,
                    "d2h_bytes": mq.d2h_bytes,
                    "quant_fingerprint": counts,
                    "restore_matches_host_twin": True}
    return info, None


def _spread(tree) -> tuple[int, int]:
    """(leaves, partitioned leaves); every leaf must have its shards on
    four distinct devices."""
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    for leaf in leaves:
        devs = {sh.device for sh in leaf.addressable_shards}
        check(len(devs) == 4, f"a leaf {leaf.shape} sits on {len(devs)} "
                              f"devices, not 4")
    return len(leaves), sum(not leaf.sharding.is_fully_replicated
                            for leaf in leaves)


def phase_four_chips(ckpt_dir: str, model_args=FULL_MODEL, steps: int = 2):
    """Sharded training on a 2x2 mesh, one save, restored onto a 4x1 mesh
    and onto one device, each bit for bit."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from repro.core import CheckpointManager
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_trainer, parse_args
    from repro.sharding.partition import Partitioner
    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, need 4")
    trainer = build_trainer(parse_args(train_argv(
        model_args, ckpt_dir, steps, steps, "--mesh", "2x2")))
    try:
        out = trainer.run()
    finally:
        trainer.close()
    losses = _losses(out)
    check(len(losses) == steps and bool(np.all(np.isfinite(losses))),
          f"losses {losses}")
    leaves, parted = _spread(out["state"])
    saved = jax.device_get(out["state"])
    info = {"arch": trainer.cfg.name, "layers": trainer.cfg.num_layers,
            "d_model": trainer.cfg.d_model, "steps": steps,
            "losses": losses, "run_wall_s": out["wall_seconds"],
            "ckpt_blocking_s": out["ckpt_blocking_seconds"],
            "mesh_2x2": {"leaves": leaves, "partitioned": parted}}
    del out

    shard_4x1 = Partitioner(trainer.cfg, make_host_mesh(4, 1)) \
        .train_state_shardings(saved)
    one = SingleDeviceSharding(jax.devices()[0])
    targets = {
        "mesh_4x1": jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            saved, shard_4x1),
        "one_device": jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            saved),
    }
    with CheckpointManager(ckpt_dir) as mgr:
        for name, tmpl in targets.items():
            t0 = time.perf_counter()
            got = mgr.restore(state_template={"train": tmpl},
                              step=steps)["train"]
            jax.block_until_ready(got)
            rec = {"restore_s": time.perf_counter() - t0}
            if name == "mesh_4x1":
                rec["leaves"], rec["partitioned"] = _spread(got)
                check(rec["partitioned"] > 0, "4x1 restore sharded nothing")
            else:
                check(all(leaf.devices() == {jax.devices()[0]}
                          for leaf in jax.tree_util.tree_leaves(got)),
                      "one-device restore left its device")
            diff = same_tree(jax.device_get(got), saved)
            check(not diff, f"{name} restore differs at {diff[:5]}")
            rec["bit_exact"] = True
            info[name] = rec
            del got
    return info, None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Smoke run of the trainer and its kernels on a TPU.")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh phase (needs four chips)")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    clock = CompileClock()
    shutil.rmtree(CKPT_ROOT, ignore_errors=True)
    try:
        run_phase("device", clock, phase_device, CKPT_ROOT)
        if args.four_chips:
            run_phase("four_chips", clock, phase_four_chips,
                      os.path.join(CKPT_ROOT, "four_chips"))
        else:
            run_phase("kernels", clock, phase_kernels)
            trainer, state = run_phase("trainer", clock, phase_trainer,
                                       os.path.join(CKPT_ROOT, "train"))
            run_phase("delta", clock, phase_delta, CKPT_ROOT, trainer, state)
    finally:
        shutil.rmtree(CKPT_ROOT, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
