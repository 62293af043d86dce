"""Small shared utilities for the TPU engine."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def tree_where(pred, on_true, on_false):
    """Elementwise select over two identical pytrees; `pred` is a scalar or
    lane-vector broadcast against each leaf's leading dim."""

    def sel(a, b):
        p = pred
        # broadcast pred over trailing dims
        while p.ndim < a.ndim:
            p = p[..., None]
        return jnp.where(p, a, b)

    return jax.tree.map(sel, on_true, on_false)


def set2d(arr, i, j, value):
    """`arr.at[i, j].set(value)` for traced (i, j) via an outer mask —
    XLA's scatter emitter rejects multi-operand dynamic indices (and the
    mask form vectorizes better under vmap anyway)."""
    n0, n1 = arr.shape
    mask = (jnp.arange(n0)[:, None] == i) & (jnp.arange(n1)[None, :] == j)
    return jnp.where(mask, value, arr)


def tree_stack_fields(tree, n):
    """Broadcast each leaf to a leading dim of n (used to replicate an
    initial node state over N nodes)."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + jnp.shape(x)), tree)


def device_info() -> dict:
    """The device work is placed on, as jax reports it: platform,
    device_kind and how many such devices are visible. Every stream
    summary, benchmark line and stats record carries it, so a number can
    never be read without the device it came from. Honors an active
    `jax.default_device(...)` (the CPU replay/oracle paths)."""
    dev = jax.config.jax_default_device or jax.devices()[0]
    if isinstance(dev, str):
        dev = jax.devices(dev)[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices(dev.platform)),
    }
