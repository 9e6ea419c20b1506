"""Plain float32 reference of the Granite 4.0-H hybrid decoder, and the
benchmark's weights for it.

Follows the published description (``GraniteMoeHybridForCausalLM`` as its
``config.json`` sets it, with no experts): the token embedding times
``embedding_multiplier``; per layer, of the kind ``layer_types`` names, a
pre-RMSNorm mixer and a pre-RMSNorm SwiGLU MLP, each branch times
``residual_multiplier`` before its residual add; the final RMSNorm; the tied
embedding as the head, its logits divided by ``logits_scaling``; next-token
cross entropy over the vocabulary the configuration holds.

- ``attention``: grouped-query attention without bias and without any
  positional encoding (``position_embedding_type`` ``nope``), a causal
  softmax over ``q.k * attention_multiplier``, the output projection.
- ``mamba``: Mamba2 with one group. ``in_proj`` gives (z, x, B, C, dt) in
  that order; a causal depthwise conv of width ``mamba_d_conv`` with bias
  over (x, B, C), written as shifted sums, then silu; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; the state-space model
  ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t h_t + D x_t``;
  the gated RMSNorm ``rmsnorm(y * silu(z)) * gain`` over all d_inner
  channels; ``out_proj``.

The state-space model is computed in its chunked dual form, chunks of
``mamba_chunk_size``: inside a chunk the masked quadratic form
``(L o C B^T) (dt x)`` with ``L[i, j] = exp(sum_{j<t<=i} dt_t A)``, between
chunks the state passed on. That is another algorithm than a token
recurrence, on purpose. Every matrix product runs at ``Precision.HIGHEST``
in float32 (``mm``). Nothing here imports the program.

Weights (:func:`stacked_weights`): ``embed`` (V, d) and ``final_norm``
(d,); the leaves of each layer kind stacked over that kind's layers in
order, named ``m.<leaf>`` and ``a.<leaf>`` (:func:`layer_leaves`): matrices
as (in, out), heads split out of the attention projections, norm gains as
multipliers (ones). Leaf ``i`` of layer ``l`` is drawn from
``fold_in(fold_in(key, i), l)``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference.qwen2 import exact_mm, rms_norm

CONV_BIAS_STD = 0.1
DT_MIN, DT_MAX = 1e-3, 1e-1
A_MIN, A_MAX = 1.0, 16.0


def dims(c: dict) -> dict:
    d = c["hidden_size"]
    hq = c["num_attention_heads"]
    d_in = c["mamba_expand"] * d
    assert c["mamba_n_groups"] == 1, "one group of B and C"
    assert c["mamba_n_heads"] * c["mamba_d_head"] == d_in
    kinds = tuple(c["layer_types"][: c["num_hidden_layers"]])
    return dict(d=d, hq=hq, hkv=c["num_key_value_heads"], hd=c.get("head_dim") or d // hq,
                f=c["shared_intermediate_size"], v=c["vocab_size"], eps=c["rms_norm_eps"],
                d_in=d_in, h=c["mamba_n_heads"], p=c["mamba_d_head"], n=c["mamba_d_state"],
                w=c["mamba_d_conv"], q=c["mamba_chunk_size"], kinds=kinds,
                n_mamba=kinds.count("mamba"), n_attn=kinds.count("attention"))


def layer_leaves(c: dict) -> Dict[str, tuple]:
    """name -> (shape, init); init is a std, ``None`` for gains of ones, or
    the name of a special draw."""
    m = dims(c)
    d, f, d_in, h, n = m["d"], m["f"], m["d_in"], m["h"], m["n"]
    hq, hkv, hd = m["hq"], m["hkv"], m["hd"]
    mlp = {"norm2": ((d,), None), "w_gate": ((d, f), d ** -0.5),
           "w_up": ((d, f), d ** -0.5), "w_down": ((f, d), f ** -0.5)}
    conv_ch = d_in + 2 * n
    mamba = {
        "norm1": ((d,), None),
        "in_proj": ((d, 2 * d_in + 2 * n + h), d ** -0.5),
        "conv_w": ((m["w"], conv_ch), m["w"] ** -0.5),
        "conv_b": ((conv_ch,), CONV_BIAS_STD),
        "dt_bias": ((h,), "dt_bias"),
        "A_log": ((h,), "A_log"),
        "D": ((h,), None),
        "ssm_norm": ((d_in,), None),
        "out_proj": ((d_in, d), d_in ** -0.5),
        **mlp,
    }
    attn = {
        "norm1": ((d,), None),
        "wq": ((d, hq, hd), d ** -0.5),
        "wk": ((d, hkv, hd), d ** -0.5),
        "wv": ((d, hkv, hd), d ** -0.5),
        "wo": ((hq, hd, d), (hq * hd) ** -0.5),
        **mlp,
    }
    return {**{f"m.{k}": v for k, v in mamba.items()}, **{f"a.{k}": v for k, v in attn.items()}}


def _leaf(key, i, layer, shape, init, dtype):
    if init is None:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(jax.random.fold_in(key, i), layer)
    if init == "dt_bias":  # inverse softplus of dt, log-uniform in [DT_MIN, DT_MAX]
        u = jax.random.uniform(k, shape, jnp.float32)
        dt = jnp.exp(math.log(DT_MIN) + u * (math.log(DT_MAX) - math.log(DT_MIN)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if init == "A_log":  # A uniform in [A_MIN, A_MAX]
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, A_MIN, A_MAX)).astype(dtype)
    return (jax.random.normal(k, shape, jnp.float32) * init).astype(dtype)


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _stacked(key, cfg_items, dtype):
    c = dict(cfg_items)
    m = dims(c)
    out = {"embed": _leaf(key, 1000, 0, (m["v"], m["d"]), m["d"] ** -0.5, dtype),
           "final_norm": jnp.ones((m["d"],), dtype)}
    for i, (name, (shape, init)) in enumerate(layer_leaves(c).items()):
        count = m["n_mamba"] if name.startswith("m.") else m["n_attn"]
        out[name] = jax.vmap(lambda l: _leaf(key, i, l, shape, init, dtype))(jnp.arange(count))
    return out


def _items(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "shared_intermediate_size", "vocab_size", "num_hidden_layers", "rms_norm_eps",
            "mamba_expand", "mamba_n_groups", "mamba_n_heads", "mamba_d_head",
            "mamba_d_state", "mamba_d_conv", "mamba_chunk_size")
    return tuple((k, c[k]) for k in keys) + (("layer_types", tuple(c["layer_types"])),)


def stacked_weights(key, c: dict, dtype) -> dict:
    """All weights in one jitted call."""
    return _stacked(key, _items(c), jnp.dtype(dtype).name)


# -- mixers --------------------------------------------------------------------

def attention(p, h, c: dict, mm=exact_mm, q_chunk: int = 512):
    """NoPE causal GQA on the normed input h (B, S, d); queries in chunks."""
    m = dims(c)
    b, s, _ = h.shape
    group = m["hq"] // m["hkv"]
    q = mm("bsd,dnh->bsnh", h, p["wq"])
    k = jnp.repeat(mm("bsd,dnh->bsnh", h, p["wk"]), group, axis=2)
    v = jnp.repeat(mm("bsd,dnh->bsnh", h, p["wv"]), group, axis=2)
    qc = min(q_chunk, s)
    assert s % qc == 0, (s, qc)
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, axis=1)
        scores = mm("bqhd,bkhd->bhqk", qb, k) * c["attention_multiplier"]
        qpos = i * qc + jnp.arange(qc)
        scores = jnp.where(kpos[None, :] <= qpos[:, None], scores, -jnp.inf)
        return mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    o = jnp.moveaxis(jax.lax.map(block, jnp.arange(s // qc)), 0, 1).reshape(
        b, s, m["hq"], m["hd"])
    return mm("bsnh,nhd->bsd", o, p["wo"])


def causal_conv(u, w, bias):
    """Depthwise causal conv as shifted sums: ``out[t] = bias + sum_k w[k]
    u[t - (W - 1) + k]``, zeros before the sequence. u (B, S, C), w (W, C)."""
    width, s = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    return bias + sum(w[k] * padded[:, k:k + s] for k in range(width))


def ssd(x, dt, a, bm, cm, chunk: int, mm=exact_mm):
    """The state-space model in chunked dual form. x (B, S, H, P), dt (B, S,
    H), a (H,) negative, bm and cm (B, S, N). Returns y (B, S, H, P)
    without the D skip."""
    b, s, h, p = x.shape
    q = min(chunk, s)
    assert s % q == 0, (s, q)
    nc = s // q
    split = lambda t: t.reshape((b, nc, q) + t.shape[2:])
    xdt = split(x * dt[..., None])                                   # (B,c,Q,H,P)
    la = split(dt * a)                                               # (B,c,Q,H)
    bm, cm = split(bm), split(cm)                                    # (B,c,Q,N)
    cum = jnp.cumsum(la, axis=2)                                     # (B,c,Q,H)
    # inside a chunk: y_i = sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]             # (B,c,i,j,H)
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    scores = mm("bcin,bcjn->bcij", cm, bm)[..., None] * decay        # (B,c,i,j,H)
    y_in = mm("bcijh,bcjhp->bcihp", scores, xdt)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)                        # (B,c,Q,H)
    own = mm("bcjn,bcjhp->bchnp", bm, xdt * to_end[..., None])       # (B,c,H,N,P)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                          # (B,c,H)

    def carry(state, xs):
        own_c, dec_c = xs
        return state * dec_c[:, :, None, None] + own_c, state

    _, entering = jax.lax.scan(carry, jnp.zeros((b, h, bm.shape[-1], p), jnp.float32),
                               (jnp.moveaxis(own, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                          # (B,c,H,N,P)
    y_from = mm("bcin,bchnp->bcihp", cm, entering) * jnp.exp(cum)[..., None]
    return (y_in + y_from).reshape(b, s, h, p)


def mamba(p, h, c: dict, mm=exact_mm):
    """The Mamba2 mixer on the normed input h (B, S, d)."""
    m = dims(c)
    b, s, _ = h.shape
    d_in, n, nh = m["d_in"], m["n"], m["h"]
    proj = mm("bsd,dk->bsk", h, p["in_proj"])
    z = proj[..., :d_in]
    xbc = proj[..., d_in:2 * d_in + 2 * n]
    dt = proj[..., 2 * d_in + 2 * n:]
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x = xbc[..., :d_in].reshape(b, s, nh, m["p"])
    bm, cm = xbc[..., d_in:d_in + n], xbc[..., d_in + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd(x, dt, -jnp.exp(p["A_log"]), bm, cm, m["q"], mm) + x * p["D"][:, None]
    y = rms_norm(y.reshape(b, s, d_in) * jax.nn.silu(z), p["ssm_norm"], m["eps"])
    return mm("bsk,kd->bsd", y, p["out_proj"])


# -- the model -----------------------------------------------------------------

def layer(p, x, kind: str, c: dict, mm=exact_mm):
    """One decoder layer on x (B, S, d) float32."""
    m = dims(c)
    r = c["residual_multiplier"]
    h = rms_norm(x, p["norm1"], m["eps"])
    x = x + r * (attention(p, h, c, mm) if kind == "attention" else mamba(p, h, c, mm))
    h = rms_norm(x, p["norm2"], m["eps"])
    g = mm("bsd,df->bsf", h, p["w_gate"])
    u = mm("bsd,df->bsf", h, p["w_up"])
    return x + r * mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"])


def forward(w, tokens, c: dict, mm=exact_mm):
    """Full forward with stacked weights ``w`` (float32): logits (B, S, V)."""
    m = dims(c)
    x = w["embed"][tokens] * c["embedding_multiplier"]
    seen = {"m": 0, "a": 0}
    for kind in m["kinds"]:
        tag = "a" if kind == "attention" else "m"
        i = seen[tag]
        seen[tag] += 1
        p = {k[2:]: v[i] for k, v in w.items() if k.startswith(tag + ".")}
        x = jax.checkpoint(lambda p, x, kind=kind: layer(p, x, kind, c, mm))(p, x)
    h = rms_norm(x, w["final_norm"], m["eps"])
    return mm("bsd,vd->bsv", h, w["embed"]) / c["logits_scaling"]


def lm_loss(w, tokens, c: dict, mm=exact_mm):
    """Mean next-token cross entropy over positions 0..S-2."""
    lg = forward(w, tokens, c, mm)[:, :-1]
    labels = tokens[:, 1:]
    lse = jax.nn.logsumexp(lg, axis=-1)
    true = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - true)
