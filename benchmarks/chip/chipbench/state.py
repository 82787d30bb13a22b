"""State and traffic made from ``--seed``, and what is read off the state.

The weights are the benchmark's, not the program's: one jitted call makes
every leaf of the training state on the device, in the dtype the program
keeps it in, from the seed alone. The plain reference makes the same
weights with the same function, so it takes nothing the program made.
Only the tree's layout (names, shapes, dtypes) comes from the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def weights_key(seed: int):
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def init_leaf(key, name: str, shape, dtype):
    """One parameter leaf, by the init rule its name calls for."""
    if name == "scale":
        x = jnp.ones(shape, jnp.float32)
    elif name == "b_f":                      # sLSTM forget-gate bias
        x = jnp.ones(shape, jnp.float32)
    elif name == "b_fgate":                  # mLSTM forget-gate bias
        x = jnp.full(shape, 3.0, jnp.float32)
    elif name.startswith("b"):
        x = jnp.zeros(shape, jnp.float32)
    else:
        if name in ("embed", "w_igate", "w_fgate") or name.startswith("r_"):
            std = 0.02
        else:
            std = 1.0 / math.sqrt(shape[-2])
        x = jax.random.normal(key, shape, jnp.float32) * std
    return x.astype(dtype)


def make_params(key, params_shape, dtype_override=None):
    """Params with the layout of ``params_shape`` (leaves with shape and
    dtype), from ``key = weights_key(seed)``. Traced: call it inside
    ``jax.jit`` with the key as an argument, so one program serves every
    seed."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(params_shape)
    leaves = []
    for i, (path, sds) in enumerate(flat):
        dt = dtype_override or sds.dtype
        # round through the program's dtype so both sides see one value
        x = init_leaf(jax.random.fold_in(key, i), leaf_name(path),
                      sds.shape, sds.dtype)
        leaves.append(x.astype(dt))
    return jax.tree_util.tree_unflatten(tdef, leaves)


def make_train_state(key, state_shape):
    """The whole training state: params, zero AdamW moments, step 0.
    Traced: call it inside ``jax.jit``."""
    params = make_params(key, state_shape["params"])

    def zeros(sds):
        return jnp.zeros(sds.shape, sds.dtype)

    rest = {k: jax.tree_util.tree_map(zeros, v)
            for k, v in state_shape.items() if k != "params"}
    return {"params": params, **rest}


def batch_at(seed: int, step: int, batch: int, seq_len: int, vocab: int,
             zipf_a: float) -> dict[str, np.ndarray]:
    """Tokens of training step ``step`` (1-based): ``batch`` rows of
    ``seq_len + 1`` Zipf-distributed ids, split into inputs and labels.
    Every row of every step is drawn afresh, so no two rows repeat."""
    rng = np.random.default_rng([int(seed) % (1 << 64), int(step)])
    ids = rng.zipf(zipf_a, size=(batch, seq_len + 1)) % (vocab - 1) + 1
    ids = ids.astype(np.int32)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


# ------------------------------------------------------ reads of the state
def _words(x):
    """Leaf bits as uint32 words, in the leaf's shape."""
    if x.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    if x.dtype.itemsize == 2:
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    if x.dtype.itemsize == 1:
        return jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
    raise TypeError(f"no fingerprint for {x.dtype}")


def _weights(shape):
    """2 * (row-major position) + 1 of every element, mod 2**32, built
    without flattening, so that a sharded leaf is read where it lies."""
    pos = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for axis in reversed(range(len(shape))):
        pos = pos + jax.lax.broadcasted_iota(jnp.uint32, shape, axis) \
            * jnp.uint32(stride % (1 << 32))
        stride *= shape[axis]
    return pos * jnp.uint32(2) + jnp.uint32(1)


def fingerprint(tree):
    """Per leaf, (sum of words, position-weighted sum of words) mod 2**32:
    any changed bit changes the first, any moved word the second."""
    out = []
    for x in jax.tree_util.tree_leaves(tree):
        w = _words(x)
        out.append(jnp.stack([jnp.sum(w, dtype=jnp.uint32),
                              jnp.sum(w * _weights(w.shape),
                                      dtype=jnp.uint32)]))
    return jnp.stack(out)


def leaf_norms(tree):
    """f32 norm of every leaf, in tree order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def grad_norms_from_moment(mu_tree, b1: float):
    """The first step's gradient as AdamW got it (clipped), per leaf:
    after one step from zero moments, mu = (1 - b1) * g."""
    inv = 1.0 / (1.0 - b1)
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(m * inv)))
                      for m in jax.tree_util.tree_leaves(mu_tree)])


def update_norms(key, params):
    """Per leaf, the norm of the params' change since the seed's initial
    values (made again here, not kept)."""
    p0 = make_params(key, params)
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(p0))])


def param_paths(params_shape) -> list[str]:
    return [path_str(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(params_shape)[0]]
