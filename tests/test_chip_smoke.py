"""CPU rehearsal of chip_smoke.py: every phase at a tiny size.

The kernels run in the Pallas interpreter and the trainer runs a
scaled-down xlstm, so each later change rehearses the chip script here
before it spends chip time. The four-chip phase runs on four virtual CPU
devices in a child process (this process keeps one device).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY_MODEL = ("--arch", "xlstm-350m", "--layers", "2", "--width-div", "16",
              "--vocab", "512", "--seq-len", "32", "--batch", "2")


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_phase_device(tmp_path):
    info, _ = chip_smoke.phase_device(str(tmp_path))
    assert info["platform"] == "cpu" and info["count"] >= 1
    assert info["io_backend"] in ("uring", "threadpool")
    assert isinstance(info["o_direct"], bool)
    assert not os.path.exists(tmp_path / ".o_direct_probe")


def test_phase_kernels_interpret():
    info, _ = chip_smoke.phase_kernels(nbytes=1 << 20, chunk_bytes=16 << 10,
                                       interpret=True)
    assert info["bit_identical"] and info["digest_chunks"] == 64


def test_phase_trainer_then_delta(tmp_path):
    info, (trainer, state) = chip_smoke.phase_trainer(
        str(tmp_path / "train"), TINY_MODEL)
    assert info["restored_bit_exact"] and info["resumed_from"] == 4
    assert info["committed_steps"] == [2, 4]
    assert np.isfinite(info["resumed_loss"])
    info, _ = chip_smoke.phase_delta(str(tmp_path), trainer, state)
    assert info["delta_restore_bit_exact"]
    assert info["save_1"]["chunks_dirty"] > 0
    assert info["int8"]["quantized_leaves"] > 0
    assert info["int8"]["restore_matches_host_twin"]


def test_host_twin_matches_kernel_math():
    from repro.kernels import ref
    x = np.random.default_rng(3).standard_normal((64, 512)) \
        .astype(np.float32)
    x[5] = 0.0                                     # all-zero row: scale 1
    q, s = chip_smoke.quantize_host(x)
    qr, sr = ref.quantize_blocks_ref(x)
    assert chip_smoke.same_bits(q, qr) and chip_smoke.same_bits(s, sr)


def test_four_chip_phase_on_virtual_devices(tmp_path):
    code = (
        "import json, sys; sys.path.insert(0, sys.argv[1]); "
        "import chip_smoke; "
        "info, _ = chip_smoke.phase_four_chips(sys.argv[2], "
        "tuple(sys.argv[3:])); print(json.dumps(info, default=str))")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", code, REPO,
                        str(tmp_path / "four"), *TINY_MODEL],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    info = json.loads(r.stdout.strip().splitlines()[-1])
    assert info["mesh_4x1"]["bit_exact"] and info["one_device"]["bit_exact"]
    assert info["mesh_4x1"]["partitioned"] > 0
    assert info["mesh_2x2"]["partitioned"] > 0


def test_compile_cache_dir_from_env(monkeypatch, tmp_path):
    import jax
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_default(monkeypatch):
    import jax
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.use_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("rows", [64, 72, 8])
def test_tpu_dispatch_rehearsal(rows, monkeypatch):
    """The TPU branches of fingerprint_digests / quant_fingerprint, with
    the kernels in the interpreter: whole chunks through the fused kernel,
    a ragged tail, and a tensor too small for one chunk (the oracle)."""
    import functools

    import jax
    from repro.core import trace
    from repro.kernels import fingerprint as fpk
    cb = 16 << 10
    for name in ("quantize_fingerprint_blocks", "quantize_blocks",
                 "fingerprint_chunks"):
        monkeypatch.setattr(fpk, name, functools.partial(
            getattr(fpk, name), interpret=True))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = np.random.default_rng(rows).standard_normal((rows, 512)) \
        .astype(np.float32)
    qh, sh = chip_smoke.quantize_host(x)
    trace.enable()
    try:
        q, s, d = fpk.quant_fingerprint(jax.numpy.asarray(x), cb)
        dig = fpk.fingerprint_digests(jax.numpy.asarray(x).reshape(-1), cb)
        counts = trace.active().counters()
    finally:
        trace.disable()
    stream = np.concatenate([qh.reshape(-1).view(np.uint8), sh.view(np.uint8)])
    assert chip_smoke.same_bits(q, qh) and chip_smoke.same_bits(s, sh)
    assert chip_smoke.same_bits(d, fpk.fingerprint_chunks_host(stream, cb))
    assert chip_smoke.same_bits(dig, fpk.fingerprint_chunks_host(
        x.reshape(-1).view(np.uint8), cb))
    took = "kernel" if rows * 512 >= cb else "oracle"
    assert counts == {f"quant_fingerprint.{took}": 1.0}
