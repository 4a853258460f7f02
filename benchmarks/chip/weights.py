"""Weights from the seed, made by the benchmark and not by the program.

The program only says which leaves it takes and their shapes (its ``init``
traced abstractly, never run); every value is drawn here, on the device, in
one jitted call.  The reference reads the same tree, so the program and the
reference share inputs and nothing the program computed.
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

Rule = Callable[[str, tuple], Dict[str, Any]]


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in path)


def make(shapes, rule: Rule, seed: int):
    """Fill an abstract tree (``jax.eval_shape`` of an init) leaf by leaf.

    ``rule(name, shape)`` returns ``{"kind": "ones" | "zeros" | "normal",
    "std": s}`` for the leaf at ``name`` ("layers/attn/wq", ...)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(leaf_name(p), s.shape, s.dtype) for p, s in flat]

    def build(key):
        out = []
        for i, (name, shape, dtype) in enumerate(specs):
            r = rule(name, shape)
            if r["kind"] == "ones":
                out.append(jnp.ones(shape, dtype))
            elif r["kind"] == "zeros":
                out.append(jnp.zeros(shape, dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * r["std"]).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jax.random.PRNGKey(seed))


def lm_rule(init: Dict[str, Any]) -> Rule:
    """Llama-style initialisation: N(0, std) for every matrix, norms at one."""
    std = float(init["std"])

    def rule(name: str, shape: tuple) -> Dict[str, Any]:
        last = name.rsplit("/", 1)[-1]
        if last == "scale":
            return {"kind": "ones"}
        if last == "bias":
            return {"kind": "zeros"}
        return {"kind": "normal", "std": std}
    return rule


def cnn_rule(init: Dict[str, Any]) -> Rule:
    """He initialisation of every [fan_in, out] matrix; biases at zero."""
    gain = float(init.get("gain", 2.0))

    def rule(name: str, shape: tuple) -> Dict[str, Any]:
        if name.endswith("/b"):
            return {"kind": "zeros"}
        return {"kind": "normal", "std": (gain / shape[0]) ** 0.5}
    return rule
