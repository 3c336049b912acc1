"""Weights for a dense decoder, made on the device from the seed.

The benchmark makes the weights itself, in one jitted call, in the
layout of the program's weight store (one tree per pipeline unit:
``embed``, ``block_000`` ... and ``final``).  The same call, on the same
seed, gives the plain reference its weights after the window, so the
reference takes nothing that the program made.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

PyTree = Any


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "K": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "f": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"]}


def layout(cfg: Dict[str, Any]) -> Dict[str, Dict[str, Tuple[int, ...]]]:
    """unit -> leaf path -> shape."""
    m = dims(cfg)
    d, H, K, dh, f, V = m["d"], m["H"], m["K"], m["dh"], m["f"], m["V"]
    block = {"norm1/scale": (d,), "attn/wq": (d, H, dh),
             "attn/wk": (d, K, dh), "attn/wv": (d, K, dh),
             "attn/wo": (H, dh, d), "norm2/scale": (d,),
             "mlp/wg": (d, f), "mlp/wu": (d, f), "mlp/wd": (f, d)}
    out = {"embed": {"tok": (V, d)}}
    for j in range(m["L"]):
        out[f"block_{j:03d}"] = dict(block)
    out["final"] = {"norm/scale": (d,)}
    if not cfg["tie_word_embeddings"]:
        out["final"]["head/w"] = (d, V)
    return out


def _std(path: str, shape: Tuple[int, ...]) -> float:
    """Scale of each leaf: embeddings 0.02, norm scales 0.1 around the
    ``1 + scale`` gain, matrices He-normal over their contracted axes."""
    if path == "tok":
        return 0.02
    if path.endswith("scale"):
        return 0.1
    fan_in = shape[0] * shape[1] if path == "attn/wo" else shape[0]
    return math.sqrt(2.0 / fan_in)


def _nest(flat: Dict[str, jax.Array]) -> PyTree:
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def seed_key(seed: int) -> jax.Array:
    """A key for any whole seed, including those past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make(cfg: Dict[str, Any], seed: int, dtype=jnp.float32
         ) -> Dict[str, PyTree]:
    """unit -> nested param tree, on the default device, in ``dtype``."""
    lay = layout(cfg)
    names = [(u, p) for u in lay for p in lay[u]]

    def build(key):
        keys = jax.random.split(key, len(names))
        flat: Dict[str, Dict[str, jax.Array]] = {u: {} for u in lay}
        for k, (u, p) in zip(keys, names):
            shape = lay[u][p]
            flat[u][p] = (jax.random.normal(k, shape, jnp.float32)
                          * _std(p, shape)).astype(dtype)
        return {u: _nest(v) for u, v in flat.items()}

    return jax.jit(build)(seed_key(seed))
