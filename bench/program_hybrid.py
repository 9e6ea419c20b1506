"""The seam between the benchmark and the program for the Granite 4.0-H
hybrid: the program's model built from its registry entry, checked width by
width against the configuration file, then cut to the file's depth (the
first layers of ``layer_types``, in order) and vocabulary; the weights of
``bench/reference/granite_hybrid.py`` laid out as the program's tree; and
the map from each reference leaf to the program's leaves that hold it.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.reference import granite_hybrid as ref

# configuration-file key -> program ModelConfig attribute
WIDTHS = {
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "shared_intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "mamba_d_state": "ssm_state",
    "mamba_expand": "ssm_expand",
    "mamba_d_head": "ssm_head_dim",
    "mamba_d_conv": "ssm_conv_width",
    "embedding_multiplier": "embed_scale",
    "residual_multiplier": "residual_scale",
    "attention_multiplier": "attn_scale",
    "logits_scaling": "logit_scale",
}
MIXER = {"mamba": "mamba2", "attention": "attn"}
# reference leaf -> its path in a program block, by layer kind ("m", "a")
_BLOCK = {"norm1": ("norm1", "scale"), "norm2": ("norm2", "scale"),
          **{k: ("mlp", k) for k in ("w_gate", "w_up", "w_down")}}
PROGRAM_NAME = {
    "m": {**_BLOCK, "ssm_norm": ("mamba", "norm_scale"),
          **{k: ("mamba", k) for k in ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                                       "out_proj")}},
    "a": {**_BLOCK, **{k: ("attn", k) for k in ("wq", "wk", "wv", "wo")}},
}
GAINS = ("norm1", "norm2", "ssm_norm", "final_norm")  # kept as 1 + scale by the program


def _kinds(cfg):
    return [b.mixer for s in cfg.segments for _ in range(s.repeat) for b in s.body]


def _first_layers(segments, n: int):
    """The segments that hold the first ``n`` layers, the last one cut."""
    out, left = [], n
    for seg in segments:
        if left <= 0:
            break
        assert len(seg.body) == 1, "one block per scan iteration"
        out.append(dataclasses.replace(seg, repeat=min(seg.repeat, left)))
        left -= out[-1].repeat
    return tuple(out)


def build(c: dict):
    """The program's model for configuration ``c``: its ``program_arch``
    entry at full widths, cut as ``reduced`` says (depth, vocabulary)."""
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config(c["program_arch"], c.get("program_variant", "full"))
    m = ref.dims(c)
    for key, attr in WIDTHS.items():
        if key not in c["reduced"] and getattr(cfg, attr) != c[key]:
            raise ValueError(f"program {attr}={getattr(cfg, attr)!r} but the configuration "
                             f"states {key}={c[key]!r}")
    d_in = cfg.ssm_expand * cfg.d_model
    if (cfg.resolved_head_dim != m["hd"] or cfg.qkv_bias != c["attention_bias"]
            or cfg.rope != (c["position_embedding_type"] != "nope")
            or d_in // cfg.ssm_head_dim != c["mamba_n_heads"]
            or _kinds(cfg) != [MIXER[k] for k in c["layer_types"]]):
        raise ValueError("program layers differ from the configuration "
                         "(head_dim / bias / positions / mamba heads / layer_types)")
    cfg = cfg.replace(segments=_first_layers(cfg.segments, c["num_hidden_layers"]),
                      vocab_size=c["vocab_size"], param_dtype=c["param_dtype"],
                      compute_dtype=c["compute_dtype"])
    return build_model(cfg)


def leaf_map(model) -> dict:
    """Reference leaf name -> [(program path, first, count)]: which rows of
    the reference's stack each program segment holds."""
    out = {"embed": [(("embed", "table"), 0, None)],
           "final_norm": [(("final_norm", "scale"), 0, None)]}
    seen = {"mamba2": 0, "attn": 0}
    for i, seg in enumerate(model.cfg.segments):
        (spec,) = seg.body
        tag = "m" if spec.mixer == "mamba2" else "a"
        for leaf, path in PROGRAM_NAME[tag].items():
            out.setdefault(f"{tag}.{leaf}", []).append(
                ((f"seg{i}", "b0") + path, seen[spec.mixer], seg.repeat))
        seen[spec.mixer] += seg.repeat
    return out


def _set(tree: dict, path: tuple, value) -> None:
    for part in path[:-1]:
        tree = tree.setdefault(part, {})
    tree[path[-1]] = value


def _get(tree: dict, path: tuple):
    for part in path:
        tree = tree[part]
    return tree


def to_program(w: dict, lmap: dict, gains: bool = True) -> dict:
    """Reference-layout weights -> the program's parameter tree. The
    reference's ``in_proj`` columns are (z, x, B, C, dt), the program's
    (x, z, B, C, dt). ``gains`` False lays out a gradient (no 1 taken off
    the norm gains)."""
    out = {}
    for name, places in lmap.items():
        leaf = w[name]
        if gains and name.split(".")[-1] in GAINS:
            leaf = leaf - 1
        if name == "m.in_proj":
            leaf = _xz_swap(leaf, w["m.out_proj"].shape[1])
        for path, first, count in places:
            _set(out, path, leaf if count is None else leaf[first:first + count])
    return out


def _xz_swap(in_proj, d_in: int):
    return jnp.concatenate([in_proj[..., d_in:2 * d_in], in_proj[..., :d_in],
                            in_proj[..., 2 * d_in:]], axis=-1)


def norms_by_leaf(lmap: dict, tree_a, tree_b, scale: float) -> dict:
    """{reference leaf: |a - b| * scale} over the program's leaves that hold
    it (the norm of their concatenation)."""
    names = list(lmap)

    def one(a, b, name):
        sq = sum(jnp.sum(jnp.square(_get(a, p).astype(jnp.float32)
                                    - _get(b, p).astype(jnp.float32)))
                 for p, _, _ in lmap[name])
        return jnp.sqrt(sq) * scale

    fn = jax.jit(lambda a, b: [one(a, b, n) for n in names])
    return dict(zip(names, (float(x) for x in fn(tree_a, tree_b))))


def program_weights(model, c: dict, key):
    """The program's parameters, made on the device in one jitted call, in
    the configuration's ``param_dtype``; checked against ``model.init``'s
    shapes and dtypes."""
    lmap = leaf_map(model)
    params = jax.jit(lambda k: to_program(ref.stacked_weights(k, c, c["param_dtype"]), lmap))(key)
    want = jax.eval_shape(lambda: model.init(jax.random.key(0))[0])
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's parameter tree")
    return params
