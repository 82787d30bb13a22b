"""Partition rules: DP × TP (× pod) with EP for MoE and ZeRO-1 moments.

Name-based rules map every parameter path to a PartitionSpec, with
divisibility guards (e.g. qwen2.5's 2 KV heads can't split 16 ways — they
replicate; internvl2's 92553 vocab shards on d_model instead). Stacked
per-group block params get a leading None for the scan axis.

DP axes: ("pod", "data") when the pod axis exists, else ("data",).
TP/EP axis: "model".
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


class Partitioner:
    def __init__(self, cfg: ModelConfig, mesh: Mesh, zero1: bool = True,
                 fsdp: bool = False):
        self.cfg = cfg
        self.mesh = mesh
        self.zero1 = zero1
        self.fsdp = fsdp   # additionally shard params over 'data' (ZeRO-3)
        self.model = axis_size(mesh, "model")
        self.dp = dp_axes(mesh)
        self.dp_size = int(np.prod([axis_size(mesh, a) for a in self.dp]))

    # ------------------------------------------------------------- params
    def param_spec(self, path: tuple[str, ...], shape: tuple[int, ...]) -> P:
        name = path[-1]
        stacked = "blocks" in path      # leading scan axis
        lead = (None,) if stacked else ()
        body = shape[1:] if stacked else shape
        m = self.model

        def guard(spec_entries):
            # verify each sharded dim divides; else replicate that entry
            out = []
            for dim, e in zip(body, spec_entries):
                out.append(e if (e is None or _div(dim, m)) else None)
            return P(*lead, *out)

        if name == "embed":
            return (P("model", None) if _div(shape[0], m)
                    else guard((None, "model")))
        if name == "head":
            return guard((None, "model"))
        if name == "frontend_proj":
            return guard((None, "model"))
        if name in ("wq", "wk", "wv", "w_up1", "w_up2", "wg", "wu", "wx",
                    "wgate", "w_input_gate", "w_a_gate", "w_up",
                    "w_i", "w_f", "w_z", "w_o", "r_i", "r_f", "r_z", "r_o"):
            if len(body) == 3:   # MoE expert-stacked (E, d, f): EP on experts
                return guard(("model", None, None))
            return guard((None, "model"))
        if name in ("wo", "wd", "w_down", "wout"):
            if len(body) == 3:   # MoE (E, f, d)
                return guard(("model", None, None))
            return guard(("model", None))
        if name == "router":
            return guard((None, None))
        if name in ("bq", "bk", "bv", "a_param", "b_input_gate", "b_a_gate"):
            return guard(("model",))
        if name in ("b_i", "b_f", "b_z", "b_o", "b_igate", "b_fgate",
                    "w_igate", "w_fgate"):
            return guard(tuple(None for _ in body))
        if name == "scale":
            return P(*lead, *(None for _ in body))
        # default: replicate
        return P(*lead, *(None for _ in body))

    def _fsdp_spec(self, pspec: P, shape: tuple[int, ...],
                   stacked: bool) -> P:
        """ZeRO-3: add 'data' to the first unsharded divisible dim, skipping
        the leading layer-stack dim (sharding the scan axis would force a
        full-stack gather every scan iteration)."""
        if not self.fsdp or "data" not in self.mesh.axis_names:
            return pspec
        entries = list(pspec) + [None] * (len(shape) - len(pspec))
        dsize = axis_size(self.mesh, "data")
        start = 1 if stacked else 0
        for i in range(start, len(shape)):
            if entries[i] is None and _div(shape[i], dsize) \
                    and shape[i] >= dsize:
                entries[i] = "data"
                return P(*entries)
        return pspec

    def param_shardings(self, params_shape):
        """Pytree of NamedShardings matching a params (shape-)pytree."""
        def one(path, leaf):
            names = tuple(
                p.key if hasattr(p, "key") else str(p) for p in path)
            spec = self.param_spec(names, tuple(leaf.shape))
            spec = self._fsdp_spec(spec, tuple(leaf.shape),
                                   stacked="blocks" in names)
            return NamedSharding(self.mesh, spec)
        return jax.tree_util.tree_map_with_path(one, params_shape)

    # ------------------------------------------------------------ optimizer
    def zero1_spec(self, pspec: P, shape: tuple[int, ...]) -> P:
        """Add 'data' sharding to the first unsharded, divisible dim."""
        if not self.zero1 or "data" not in self.mesh.axis_names:
            return pspec
        entries = list(pspec) + [None] * (len(shape) - len(pspec))
        dsize = axis_size(self.mesh, "data")
        for i, (dim, e) in enumerate(zip(shape, entries)):
            if e is None and _div(dim, dsize) and dim >= dsize:
                entries[i] = "data"
                return P(*entries)
        return pspec

    def opt_shardings(self, params_shape):
        def one(path, leaf):
            names = tuple(
                p.key if hasattr(p, "key") else str(p) for p in path)
            shape = tuple(leaf.shape)
            ps = self.param_spec(names, shape)
            if self.fsdp:   # ZeRO-3: moments follow the fsdp param sharding
                ps = self._fsdp_spec(ps, shape, stacked="blocks" in names)
            else:           # ZeRO-1: shard moments over data
                ps = self.zero1_spec(ps, shape)
            return NamedSharding(self.mesh, ps)
        moments = jax.tree_util.tree_map_with_path(one, params_shape)
        return {"mu": moments, "nu": moments,
                "count": NamedSharding(self.mesh, P())}

    def train_state_shardings(self, state_shape):
        """Shardings for a whole ``init_train_state`` pytree (shapes)."""
        return {"params": self.param_shardings(state_shape["params"]),
                "opt": self.opt_shardings(state_shape["params"]),
                "step": self.replicated()}

    # ------------------------------------------------------------ activations
    def batch_spec(self) -> P:
        return P(self.dp,)

    def tokens_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.dp, None))

    def frontend_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.dp, None, None))

    def activation_spec(self) -> P:
        return P(self.dp, None, None)

    def cache_shardings(self, cache_shape):
        """Decode caches: batch over DP; KV-head dim over model if divisible."""
        def one(path, leaf):
            shape = tuple(leaf.shape)
            # stacked leading group axis, then batch
            entries: list = [None]  # group axis
            if len(shape) >= 2:
                entries.append(self.dp)
            for dim in shape[2:]:
                if dim == self.cfg.num_kv_heads and \
                        _div(self.cfg.num_kv_heads, self.model):
                    entries.append("model")
                elif dim == self.cfg.num_heads and \
                        _div(self.cfg.num_heads, self.model):
                    entries.append("model")
                else:
                    entries.append(None)
            # scalar leaves (e.g. pos)
            entries = entries[:len(shape)]
            return NamedSharding(self.mesh, P(*entries))
        return jax.tree_util.tree_map(one, cache_shape)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())
