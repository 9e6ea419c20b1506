"""Plain float32 reference of the Qwen2 decoder, and the benchmark's weights.

Follows the published Qwen2 description (``Qwen2ForCausalLM`` as its
``config.json`` sets it): token embedding; per layer pre-RMSNorm,
grouped-query attention with biases on q/k/v, rotary embeddings in the
half-rotation form, a causal softmax over ``q.k / sqrt(head_dim)``, output
projection, residual; pre-RMSNorm SwiGLU MLP, residual; final RMSNorm and
the tied embedding as the head. Every matrix product runs at
``Precision.HIGHEST`` in float32. Nothing here imports the program.

Departures, none of which change the mathematics: weights are random from
the seed, norm gains are 1, and the layout of a leaf is the one named in
:data:`LAYER_LEAVES` (heads split out of the projection axes).

Weights come from :func:`stacked_weights`: one jitted call for all layers,
in the type the configuration trains them in. Leaf ``i`` of layer ``l`` is
``normal(fold_in(fold_in(key, i), l)) * std``.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST

BIAS_STD = 0.1


def dims(c: dict) -> dict:
    d = c["hidden_size"]
    hq = c["num_attention_heads"]
    return dict(d=d, hq=hq, hkv=c["num_key_value_heads"], hd=c.get("head_dim", d // hq),
                f=c["intermediate_size"], v=c["vocab_size"], layers=c["num_hidden_layers"],
                eps=c["rms_norm_eps"], theta=c["rope_theta"])


def layer_leaves(c: dict) -> Dict[str, tuple]:
    """name -> (shape, std); std None means a norm gain of ones."""
    m = dims(c)
    d, hq, hkv, hd, f = m["d"], m["hq"], m["hkv"], m["hd"], m["f"]
    return {
        "norm1": ((d,), None),
        "wq": ((d, hq, hd), d ** -0.5),
        "wk": ((d, hkv, hd), d ** -0.5),
        "wv": ((d, hkv, hd), d ** -0.5),
        "bq": ((hq, hd), BIAS_STD),
        "bk": ((hkv, hd), BIAS_STD),
        "bv": ((hkv, hd), BIAS_STD),
        "wo": ((hq, hd, d), (hq * hd) ** -0.5),
        "norm2": ((d,), None),
        "w_gate": ((d, f), d ** -0.5),
        "w_up": ((d, f), d ** -0.5),
        "w_down": ((f, d), f ** -0.5),
    }


def _leaf(key, i, layer, shape, std, dtype):
    if std is None:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(jax.random.fold_in(key, i), layer)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def _top(key, c, dtype):
    m = dims(c)
    return {
        "embed": _leaf(key, 1000, 0, (m["v"], m["d"]), m["d"] ** -0.5, dtype),
        "final_norm": jnp.ones((m["d"],), dtype),
    }


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _stacked(key, cfg_items, dtype):
    c = dict(cfg_items)
    layers = jnp.arange(dims(c)["layers"])
    out = _top(key, c, dtype)
    for i, (name, (shape, std)) in enumerate(layer_leaves(c).items()):
        out[name] = jax.vmap(lambda l: _leaf(key, i, l, shape, std, dtype))(layers)
    return out


def _items(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads", "intermediate_size",
            "vocab_size", "num_hidden_layers", "rms_norm_eps", "rope_theta")
    items = [(k, c[k]) for k in keys]
    if "head_dim" in c:
        items.append(("head_dim", c["head_dim"]))
    return tuple(items)


def stacked_weights(key, c: dict, dtype) -> dict:
    """All weights in one jitted call: per-layer leaves stacked on axis 0."""
    return _stacked(key, _items(c), jnp.dtype(dtype).name)


# -- matrix products -----------------------------------------------------------

def exact_mm(eq: str, a, b):
    return jnp.einsum(eq, a, b, precision=HI, preferred_element_type=jnp.float32)


def _fp8(x):
    """Per-tensor scaled float8 (e4m3) round trip; the gradient passes
    straight through the rounding, as in training at float8."""
    scale = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def fp8_mm(eq: str, a, b):
    """The control's product: both operands rounded to float8 first."""
    return exact_mm(eq, _fp8(a), _fp8(b))


# -- the model -----------------------------------------------------------------

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rope(x, pos, theta):
    """x (B, S, H, hd), pos (S,): rotate_half form."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


def attention(q, k, v, mm, q_chunk: int):
    """Causal GQA attention; queries in chunks of ``q_chunk`` so the scores
    of a long sequence fit. q (B,S,Hq,hd), k/v (B,S,Hkv,hd)."""
    b, s, hq, hd = q.shape
    group = hq // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    c = min(q_chunk, s)
    assert s % c == 0, (s, c)
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * c, c, axis=1)
        scores = mm("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(jnp.float32(hd))
        qpos = i * c + jnp.arange(c)
        scores = jnp.where(kpos[None, :] <= qpos[:, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return mm("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(block, jnp.arange(s // c))  # (n, B, c, H, hd)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, hq, hd)


def layer(p, x, c: dict, mm=exact_mm, q_chunk: int = 512):
    """One decoder layer on x (B, S, d) float32."""
    m = dims(c)
    pos = jnp.arange(x.shape[1])
    h = rms_norm(x, p["norm1"], m["eps"])
    q = mm("bsd,dnh->bsnh", h, p["wq"]) + p["bq"]
    k = mm("bsd,dnh->bsnh", h, p["wk"]) + p["bk"]
    v = mm("bsd,dnh->bsnh", h, p["wv"]) + p["bv"]
    q, k = rope(q, pos, m["theta"]), rope(k, pos, m["theta"])
    o = attention(q, k, v, mm, q_chunk)
    x = x + mm("bsnh,nhd->bsd", o, p["wo"])
    h = rms_norm(x, p["norm2"], m["eps"])
    g = mm("bsd,df->bsf", h, p["w_gate"])
    u = mm("bsd,df->bsf", h, p["w_up"])
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"])


def logits(top, x, c: dict, mm=exact_mm):
    m = dims(c)
    h = rms_norm(x, top["final_norm"], m["eps"])
    return mm("bsd,vd->bsv", h, top["embed"])


def forward(w, tokens, c: dict, mm=exact_mm, q_chunk: int = 512):
    """Full forward with stacked weights ``w`` (float32): logits (B,S,V)."""
    x = w["embed"][tokens]
    per_layer = {k: v for k, v in w.items() if k not in ("embed", "final_norm")}

    def body(h, p):
        return layer(p, h, c, mm, q_chunk), None

    x, _ = jax.lax.scan(body, x, per_layer)
    return logits(w, x, c, mm)


def lm_loss(w, tokens, c: dict, mm=exact_mm):
    """Mean next-token cross entropy over positions 0..S-2."""
    lg = forward(w, tokens, c, mm)[:, :-1]
    labels = tokens[:, 1:]
    lse = jax.nn.logsumexp(lg, axis=-1)
    true = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - true)


def psgd_update(w, g, anchor, lr: float, gamma: float):
    """Penalty SGD (paper Alg. 2), the closed form of
    argmin_v g.v + |v - w|^2 / (2 lr) + |v - anchor|^2 / (2 gamma)."""
    return jax.tree.map(lambda x, gx, a: (gamma * (x - lr * gx) + lr * a) / (gamma + lr),
                        w, g, anchor)
