"""Optimizers in pure JAX (no optax offline): SGD, AdamW and Adafactor.

AdamW keeps fp32 moments regardless of param dtype (bf16-safe). Adafactor
(Shazeer & Stern 2018) factorizes the second moment per matrix — the
standard choice for trillion-parameter MoE training where full Adam
states would not fit HBM (used for the kimi-k2 config).

Implementation detail: updates flatten the pytrees once and zip leaf
lists — robust to None/state-dict leaves that break nested tree.map.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs.scopes import OPTIMIZER

__all__ = ["adamw", "adafactor", "sgd", "Optimizer", "global_norm"]


class Optimizer(NamedTuple):
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any], Tuple[Any, Any]]  # (g, state, p)


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))


def _scoped(update):
    """``update`` under the ``optimizer`` named scope (HLO metadata only),
    so profiles attribute its ops to the optimizer."""
    @functools.wraps(update)
    def scoped(grads, state, params):
        with jax.named_scope(OPTIMIZER):
            return update(grads, state, params)
    return scoped


def _clip(grads, grad_clip):
    if grad_clip is None:
        return grads
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def sgd(lr: float = 1e-2):
    def init(params):
        return {"step": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        new_p = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads)
        return new_p, {"step": state["step"] + 1}

    return Optimizer(init, _scoped(update))


def adamw(lr: float = 1e-3, b1: float = 0.9, b2: float = 0.999,
          eps: float = 1e-8, weight_decay: float = 0.0,
          grad_clip: Optional[float] = None):
    def init(params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"step": jnp.zeros((), jnp.int32),
                "m": jax.tree.map(z, params),
                "v": jax.tree.map(z, params)}

    def update(grads, state, params):
        step = state["step"] + 1
        grads = _clip(grads, grad_clip)
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)
        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state["m"])
        flat_v = treedef.flatten_up_to(state["v"])
        new_p, new_m, new_v = [], [], []
        for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
            g32 = g.astype(jnp.float32)
            m = b1 * m + (1 - b1) * g32
            v = b2 * v + (1 - b2) * jnp.square(g32)
            delta = lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                          + weight_decay * p.astype(jnp.float32))
            new_p.append((p.astype(jnp.float32) - delta).astype(p.dtype))
            new_m.append(m)
            new_v.append(v)
        return (jax.tree.unflatten(treedef, new_p),
                {"step": step,
                 "m": jax.tree.unflatten(treedef, new_m),
                 "v": jax.tree.unflatten(treedef, new_v)})

    return Optimizer(init, _scoped(update))


def adafactor(lr: float = 1e-2, decay: float = 0.8, eps: float = 1e-30,
              clip_threshold: float = 1.0,
              grad_clip: Optional[float] = None):
    """Factored second moment: O(n+m) state per n x m matrix — the HBM
    budget that lets a 1T-param MoE train on 512 chips (DESIGN.md §4)."""

    def init(params):
        flat_p, treedef = jax.tree.flatten(params)
        fac = []
        for p in flat_p:
            if p.ndim >= 2:
                fac.append({"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                            "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                            jnp.float32)})
            else:
                fac.append({"v": jnp.zeros(p.shape, jnp.float32)})
        return {"step": jnp.zeros((), jnp.int32), "fac": fac}

    def update(grads, state, params):
        step = state["step"] + 1
        grads = _clip(grads, grad_clip)
        t = step.astype(jnp.float32)
        beta = 1.0 - t ** (-decay)
        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        fac = state["fac"]
        new_p, new_fac = [], []
        for p, g, s in zip(flat_p, flat_g, fac):
            g32 = g.astype(jnp.float32)
            g2 = jnp.square(g32) + eps
            if p.ndim >= 2:
                vr = beta * s["vr"] + (1 - beta) * g2.mean(axis=-1)
                vc = beta * s["vc"] + (1 - beta) * g2.mean(axis=-2)
                r = vr / jnp.maximum(vr.mean(axis=-1, keepdims=True), eps)
                u = (g32 * jax.lax.rsqrt(r)[..., None]
                     * jax.lax.rsqrt(vc)[..., None, :])
                new_fac.append({"vr": vr, "vc": vc})
            else:
                v = beta * s["v"] + (1 - beta) * g2
                u = g32 * jax.lax.rsqrt(v)
                new_fac.append({"v": v})
            rms = jnp.sqrt(jnp.mean(jnp.square(u)) + 1e-30)
            u = u / jnp.maximum(1.0, rms / clip_threshold)
            new_p.append((p.astype(jnp.float32) - lr * u).astype(p.dtype))
        return (jax.tree.unflatten(treedef, new_p),
                {"step": step, "fac": new_fac})

    return Optimizer(init, _scoped(update))
