"""ShapeDtypeStruct input specs for every (arch × shape) dry-run cell.

No device allocation happens here: every model input, parameter, optimizer
moment and decode-cache leaf is a ShapeDtypeStruct carrying its NamedSharding,
so ``jit(...).lower(**specs).compile()`` exercises the full SPMD partitioner
without touching HBM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import transformer as T
from repro.models.config import ModelConfig, ShapeConfig
from repro.sharding.partition import Partitioner, dp_axes
from repro.train.steps import init_train_state


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _with_shardings(shape_tree, sharding_tree):
    return jax.tree_util.tree_map(
        lambda s, sh: _sds(s.shape, s.dtype, sh), shape_tree, sharding_tree)


def _batch_entry(part: Partitioner, batch: int):
    """Shard batch over DP axes only when divisible (long_500k has B=1)."""
    return part.dp if batch % max(part.dp_size, 1) == 0 else None


def train_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                fsdp: bool = True):
    """(state_specs, batch_specs, shardings) for train/prefill cells.

    Training defaults to FSDP (ZeRO-3) param sharding: at 32B-scale the
    per-layer fp32 grad accumulator otherwise exceeds per-device HBM."""
    part = Partitioner(cfg, mesh, fsdp=fsdp)
    state_shape = jax.eval_shape(
        lambda: init_train_state(jax.random.key(0), cfg))
    shardings = part.train_state_shardings(state_shape)
    state_specs = _with_shardings(state_shape, shardings)

    bdim = _batch_entry(part, shape.global_batch)
    tok_sh = NamedSharding(mesh, P(bdim, None))
    batch_specs = {
        "tokens": _sds((shape.global_batch, shape.seq_len), jnp.int32, tok_sh),
        "labels": _sds((shape.global_batch, shape.seq_len), jnp.int32, tok_sh),
    }
    if cfg.frontend:
        batch_specs["frontend_embeds"] = _sds(
            (shape.global_batch, cfg.frontend_len, cfg.frontend_dim),
            jnp.float32, NamedSharding(mesh, P(bdim, None, None)))
    return state_specs, batch_specs, shardings


def prefill_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    """(param_specs, batch_specs) for the prefill (inference fwd) cells.
    Inference keeps params TP-only (no FSDP gathers on the serving path)."""
    state_specs, batch_specs, shardings = train_specs(cfg, shape, mesh,
                                                      fsdp=False)
    return state_specs["params"], batch_specs, shardings["params"]


def serve_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    """(param_specs, cache_specs, token_specs, pos_specs) for decode cells.

    The KV/recurrent cache is sized for shape.seq_len context; the step
    decodes ONE new token (the assignment's serve_step semantics).
    """
    part = Partitioner(cfg, mesh)
    params_shape = jax.eval_shape(
        lambda: init_train_state(jax.random.key(0), cfg))["params"]
    param_shardings = part.param_shardings(params_shape)
    param_specs = _with_shardings(params_shape, param_shardings)

    B = shape.global_batch
    cache_shape = jax.eval_shape(
        lambda: T.init_cache(cfg, B, shape.seq_len))
    bdim = _batch_entry(part, B)

    kv_shardable = (cfg.num_kv_heads % part.model == 0
                    and cfg.num_kv_heads >= part.model)

    def cache_sharding(leaf):
        shp = tuple(leaf.shape)
        entries: list = []
        if len(shp) >= 1:
            entries.append(None)            # stacked group axis
        if len(shp) >= 2:
            entries.append(bdim)            # batch
        used_model = False
        for i, dim in enumerate(shp[2:], start=2):
            if used_model:
                entries.append(None)
                continue
            if dim in (cfg.num_kv_heads, cfg.num_heads) and \
                    dim % part.model == 0 and dim >= part.model:
                entries.append("model")
                used_model = True
            elif dim == cfg.lru_dim and dim % part.model == 0:
                entries.append("model")
                used_model = True
            elif (not kv_shardable and len(shp) == 5 and i == 2
                  and dim % part.model == 0 and dim > part.model):
                # K/V (G, B, W, kv, hd) with unshardable kv heads: shard the
                # cache TIMELINE over 'model' (flash-decoding style — partial
                # softmax reductions become collectives)
                entries.append("model")
                used_model = True
            else:
                entries.append(None)
        return NamedSharding(mesh, P(*entries[:len(shp)]))

    cache_shardings = jax.tree_util.tree_map(cache_sharding, cache_shape)
    cache_specs = _with_shardings(cache_shape, cache_shardings)
    tok = _sds((B, 1), jnp.int32, NamedSharding(mesh, P(bdim, None)))
    pos = _sds((B, 1), jnp.int32, NamedSharding(mesh, P(bdim, None)))
    return param_specs, cache_specs, tok, pos, param_shardings, cache_shardings


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    """Uniform entry: kind-dispatched specs for a dry-run cell."""
    if shape.kind == "train":
        return {"mode": "train", "specs": train_specs(cfg, shape, mesh)}
    if shape.kind == "prefill":
        return {"mode": "prefill", "specs": prefill_specs(cfg, shape, mesh)}
    return {"mode": "decode", "specs": serve_specs(cfg, shape, mesh)}
