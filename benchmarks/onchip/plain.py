"""Plain arithmetic of the reference, shared with the ranking models'
reference stages (``models/<ranking>.py``).

``precision="highest"`` is float32, the reference.  ``"high"`` is the
control that has to fail: every product in three bfloat16 passes
(``mm``), what the TPU's "high" precision does.  A model file forms every
product through ``mm`` or ``mlp`` with the precision it is given, so the
control reaches its ranking stage too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def bf16(x):
    """x rounded to bfloat16, kept in float32 (``reduce_precision`` is not
    folded away under jit, as a float32 -> bfloat16 -> float32 round trip
    may be)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def mm(spec: str, a, b, precision: str):
    """einsum at ``precision``: "highest" is float32; "high" is the TPU's
    three-pass bfloat16 product, emulated alike on every backend: each
    operand split into a bfloat16 head and tail, and head*head +
    head*tail + tail*head summed in float32."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision="highest")
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    ah, bh = bf16(a), bf16(b)
    al, bl = bf16(a - ah), bf16(b - bh)
    mul = lambda x, y: jnp.einsum(spec, x, y, precision="highest")
    return mul(ah, bh) + (mul(ah, bl) + mul(al, bh))


def mlp(p, x, precision: str):
    """Dense layers ``p["layers"]`` with ReLU between them, at
    ``precision``."""
    n = len(p["layers"])
    for i, lp in enumerate(p["layers"]):
        x = mm("...i,io->...o", x, lp["w"], precision) + lp["b"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def task_slice(tree, t: int):
    """Task ``t``'s parameters out of per-task stacked ones."""
    return jax.tree_util.tree_map(lambda a: a[t], tree)
