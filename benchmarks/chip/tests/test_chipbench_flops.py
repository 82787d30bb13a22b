"""The model-FLOPs count: a hand count at a small size, and never above
what XLA counts for the compiled step where every loop runs once (XLA's
cost analysis counts a loop's body once, whatever its trip count)."""

import jax
import jax.numpy as jnp
import pytest

from _util import CHIP  # noqa: F401
from chipbench import flops

XLSTM = dict(name="x", family="ssm", num_layers=2, d_model=64, num_heads=2,
             num_kv_heads=2, d_ff=0, vocab_size=512,
             block_pattern=["mlstm", "slstm"], proj_factor=2.0,
             tie_embeddings=True, remat=True)
DENSE = dict(name="d", family="dense", num_layers=1, d_model=64,
             num_heads=4, num_kv_heads=4, d_ff=96, vocab_size=512,
             tie_embeddings=False, remat=True)


def test_hand_count_xlstm():
    S, B, d, up, H, D, V = 128, 2, 64, 128, 2, 64, 512
    mlstm = 2 * S * (2 * d * up + up * d + 3 * up * up + 2 * up * H) \
        + 2 * H * D * S * (S + 1)           # one chunk: causal half
    slstm = 2 * S * (8 * d * d + 2 * d * up)
    unembed = 2 * S * d * V
    want = 3 * B * (mlstm + slstm + unembed)
    assert flops.train_flops_per_step(XLSTM, B, S) == want == 265224192


def test_hand_count_chunked_mlstm():
    """Past one chunk, C and N are read and updated at every position."""
    S, c, H, D = 512, 256, 2, 64
    m = dict(XLSTM, num_layers=2)
    one = flops.forward_flops(m, S)
    intra = 2 * 2 * H * D * c * (c + 1)
    inter = S * H * (4 * D * D + 4 * D)
    base = 2 * S * (2 * 64 * 128 + 128 * 64 + 3 * 128 * 128 + 2 * 128 * H)
    rest = 2 * S * (8 * 64 * 64 + 2 * 64 * 128) + 2 * S * 64 * 512
    assert one == base + intra + inter + rest


def test_hand_count_dense():
    S, d, H, Dh, f, V = 256, 64, 4, 16, 96, 512
    layer = 2 * S * (4 * d * d) + 2 * H * Dh * S * (S + 1) + 2 * S * 3 * d * f
    assert flops.forward_flops(DENSE, S) == layer + 2 * S * d * V


@pytest.mark.parametrize("m,B,S", [(XLSTM, 2, 128), (XLSTM, 2, 256),
                                   (DENSE, 1, 256), (DENSE, 2, 512)])
def test_not_above_xla(m, B, S):
    from repro.models.config import ModelConfig
    from repro.optim import AdamWConfig
    from repro.train.steps import init_train_state, make_train_step
    mm = dict(m, block_pattern=tuple(m.get("block_pattern") or ()))
    cfg = ModelConfig(**mm)
    state = jax.eval_shape(lambda: init_train_state(jax.random.key(0), cfg))
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32)
             for k in ("tokens", "labels")}
    compiled = jax.jit(make_train_step(cfg, AdamWConfig())) \
        .lower(state, batch).compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    assert flops.train_flops_per_step(m, B, S) <= ca["flops"]
