"""The seam between the benchmark and the program under test.

Builds the program's model from a configuration file (the program's own
registry entry, checked width by width against the file, cut to the file's
depth and dtypes), and lays the benchmark's weights out as the program's
parameter tree. This is the only file besides the drivers that imports
``repro``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.reference import qwen2

# configuration-file key -> program ModelConfig attribute
WIDTHS = {
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def build(c: dict):
    """The program's model for configuration ``c`` (its ``program_arch``
    entry at full widths, cut to ``num_hidden_layers``)."""
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config(c["program_arch"], c.get("program_variant", "full"))
    for key, attr in WIDTHS.items():
        if getattr(cfg, attr) != c[key]:
            raise ValueError(f"program {attr}={getattr(cfg, attr)!r} but the configuration "
                             f"states {key}={c[key]!r}")
    if not cfg.qkv_bias or cfg.resolved_head_dim != qwen2.dims(c)["hd"]:
        raise ValueError("program attention differs from the configuration (bias / head_dim)")
    (seg,) = cfg.segments
    seg = dataclasses.replace(seg, repeat=c["num_hidden_layers"] // len(seg.body))
    cfg = cfg.replace(segments=(seg,), param_dtype=c["param_dtype"],
                      compute_dtype=c["compute_dtype"])
    return build_model(cfg)


def to_program(w: dict) -> dict:
    """Reference-layout weights (stacked) -> the program's parameter tree.
    The program keeps RMSNorm gains as ``1 + scale``."""
    return {
        "embed": {"table": w["embed"]},
        "seg0": {"b0": {
            "norm1": {"scale": w["norm1"] - 1},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")},
            "norm2": {"scale": w["norm2"] - 1},
            "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
        }},
        "final_norm": {"scale": w["final_norm"] - 1},
    }


PROGRAM_LEAF = {
    "embed": ("embed", "table"), "final_norm": ("final_norm", "scale"),
    "norm1": ("seg0", "b0", "norm1", "scale"), "norm2": ("seg0", "b0", "norm2", "scale"),
    **{k: ("seg0", "b0", "attn", k) for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")},
    **{k: ("seg0", "b0", "mlp", k) for k in ("w_gate", "w_up", "w_down")},
}


def leaf(tree: dict, name: str):
    for part in PROGRAM_LEAF[name]:
        tree = tree[part]
    return tree


def program_weights(model, c: dict, key):
    """The program's parameters, made on the device in one jitted call, in
    the configuration's ``param_dtype``; checked against ``model.init``'s
    shapes and dtypes."""
    params = jax.jit(lambda k: to_program(qwen2.stacked_weights(k, c, c["param_dtype"])))(key)
    want = jax.eval_shape(lambda: model.init(jax.random.key(0))[0])
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's parameter tree")
    return params


def free(*objs) -> None:
    """Drop device buffers held by the given trees."""
    for o in objs:
        for x in jax.tree.leaves(o):
            if isinstance(x, jax.Array) and not x.is_deleted():
                x.delete()
