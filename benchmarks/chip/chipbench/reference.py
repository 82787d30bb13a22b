"""Three training steps of the plain reference, and what they read.

The reference model of a configuration is ``configs/<reference>.py``
(``hidden`` and ``out_weight``); this module adds the loss over all rows,
its gradient and the AdamW update, all in float32, and returns the same
readings that the benchmark takes from the program:

  * the loss of each of the first three steps;
  * per leaf, the norm of the first step's gradient as AdamW takes it
    (after global-norm clipping);
  * per leaf, the norm of the params' change after three steps.

The AdamW moments stay in host memory between steps, so the float32
params and gradients alone sit on the device. Those are spread over the
devices the run was given by a rule of this module's own (``placement``),
so that a state larger than one chip fits.
"""

from __future__ import annotations

import importlib.util
import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

from . import state as S
from .refops import Precision, ce_sum

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclass
class Readings:
    losses: list[float]
    grad_norms: np.ndarray      # per param leaf, first step, clipped
    update_norms: np.ndarray    # per param leaf, after three steps


def load_model(name: str):
    path = os.path.join(HERE, "configs", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_ref_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def placement(params_shape, devices) -> dict:
    """Where each float32 leaf lives: split over every device on its
    largest dimension that the device count divides, or whole on each."""
    devices = list(devices)
    mesh = Mesh(np.array(devices), ("d",), axis_types=(AxisType.Auto,))
    n = len(devices)

    def one(sds):
        spec = [None] * len(sds.shape)
        dims = [i for i, size in enumerate(sds.shape) if size % n == 0]
        if dims:
            spec[max(dims, key=lambda i: sds.shape[i])] = "d"
        return NamedSharding(mesh, PartitionSpec(*spec))
    return jax.tree_util.tree_map(one, params_shape)


def _loss_and_grad(ref, model: dict, prec: Precision, rows_per_block: int,
                   shardings):
    def block_loss(params, tokens, labels):
        h = ref.hidden(params, tokens, model, prec)
        return ce_sum(h, ref.out_weight(params, model), labels, prec)

    grad = jax.value_and_grad(block_loss)

    def total(params, tokens, labels):
        B, L = tokens.shape
        rb = min(rows_per_block, B)
        toks = tokens.reshape(B // rb, rb, L)
        labs = labels.reshape(B // rb, rb, L)

        def body(carry, xs):
            l, g = grad(params, *xs)
            return (carry[0] + l,
                    jax.tree_util.tree_map(jnp.add, carry[1], g)), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        (lsum, gsum), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zero), (toks, labs))
        n = tokens.size
        return lsum / n, jax.tree_util.tree_map(lambda g: g / n, gsum)

    replicated = NamedSharding(
        jax.tree_util.tree_leaves(shardings)[0].mesh, PartitionSpec())
    return jax.jit(total, out_shardings=(replicated, shardings))


@jax.jit
def _adamw_leaf(p, g, mu, nu, scale, count, lr, b1, b2, eps, wd):
    g = g * scale
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * jnp.square(g)
    mhat = mu / (1 - b1 ** count)
    vhat = nu / (1 - b2 ** count)
    p = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
    return p, mu, nu


def half_of(rows: np.ndarray) -> np.ndarray:
    """The fault "half of the batch left out": the first half of the rows,
    or of the one row's positions when the batch is a single row."""
    if rows.shape[0] >= 2:
        return rows[:rows.shape[0] // 2]
    return rows[:, :rows.shape[1] // 2]


def lr_at(opt: dict, count: int) -> float:
    warm = min(count / max(opt["warmup_steps"], 1), 1.0)
    return opt["lr"] * warm


def run_reference(config: dict, seed: int, params_shape, batches: list[dict],
                  precision: str = "f32", half_batch: bool = False,
                  devices=None) -> Readings:
    """The reference's readings over ``batches`` (one per step), on
    ``devices`` (the default device when None)."""
    ref = load_model(config["reference"])
    model, opt = config["model"], config["optimizer"]
    prec = Precision(precision)
    shardings = placement(params_shape, devices or jax.devices()[:1])
    loss_grad = _loss_and_grad(ref, model, prec,
                               config["train"]["reference_rows_per_block"],
                               shardings)
    key = S.weights_key(seed)
    init = jax.jit(lambda k: S.make_params(k, params_shape, jnp.float32),
                   out_shardings=shardings)
    params = init(key)
    leaves, tdef = jax.tree_util.tree_flatten(params)
    places = jax.tree_util.tree_leaves(shardings)
    whole = NamedSharding(places[0].mesh, PartitionSpec())
    mu = [None] * len(leaves)
    nu = [None] * len(leaves)
    losses, grad_norms = [], None
    for k, b in enumerate(batches, start=1):
        tokens, labels = b["tokens"], b["labels"]
        if half_batch:
            tokens, labels = half_of(tokens), half_of(labels)
        loss, grads = loss_grad(jax.tree_util.tree_unflatten(tdef, leaves),
                                jax.device_put(tokens, whole),
                                jax.device_put(labels, whole))
        losses.append(float(loss))
        g_leaves = jax.tree_util.tree_leaves(grads)
        gnorm = float(np.sqrt(sum(float(jnp.sum(jnp.square(g)))
                                  for g in g_leaves)))
        clip = opt["grad_clip"]
        scale = min(1.0, clip / (gnorm + 1e-9)) if clip else 1.0
        if k == 1:   # no reshape: a leaf split over devices stays where it is
            grad_norms = np.array([float(jnp.linalg.norm(g)) * scale
                                   for g in g_leaves])
        new = []
        for i, (p, g) in enumerate(zip(leaves, g_leaves)):
            m0 = (jnp.zeros_like(p) if mu[i] is None
                  else jax.device_put(mu[i], places[i]))
            v0 = (jnp.zeros_like(p) if nu[i] is None
                  else jax.device_put(nu[i], places[i]))
            p, m1, v1 = _adamw_leaf(p, g, m0, v0, np.float32(scale),
                                    np.float32(k), np.float32(lr_at(opt, k)),
                                    np.float32(opt["b1"]),
                                    np.float32(opt["b2"]),
                                    np.float32(opt["eps"]),
                                    np.float32(opt["weight_decay"]))
            new.append(p)
            if k < len(batches):
                mu[i], nu[i] = jax.device_get((m1, v1))
            del m0, v0, m1, v1
        del grads, g_leaves
        leaves = new
    p0 = jax.tree_util.tree_leaves(init(key))
    upd = np.array([float(jnp.linalg.norm(a - b))
                    for a, b in zip(leaves, p0)])
    return Readings(losses, grad_norms, upd)
