"""Mamba2 (SSD) mixer [arXiv:2405.21060], as used by zamba2-2.7b and
granite-4.0-h.

Structure: in_proj → (x, z, B, C, dt); short causal depthwise conv over
(x,B,C); selective state-space recurrence with per-head scalar decay
``a_t = exp(dt_t * A)``; gated RMSNorm ``rmsnorm(y * silu(z))``; out_proj.
B and C are one group shared by every head (``n_groups`` 1, all these
models use).

Training and prefill run the SSM in its chunked dual form (:func:`ssd`,
chunks of ``SSD_CHUNK`` tokens): matmuls inside a chunk, the state
passed only between chunks, so the backward holds chunk states and not one
state per token; prefill starts from the cache's state and leaves the final
one there. Decode takes one step of the recurrence (``gla_step``).

Decode keeps two cache entries per layer: the SSM state (B,H,state,hd) and
the rolling conv window (B, conv_w-1, conv_channels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.layers.linear_attention import gla_step
from repro.sharding import constrain
from repro.utils.prng import fold_in_name


SSD_CHUNK = 256  # Mamba2's chunk_size, as granite-4.0-h and zamba2 publish it


def _dims(cfg):
    d_in = cfg.ssm_expand * cfg.d_model
    nh = d_in // cfg.ssm_head_dim
    conv_ch = d_in + 2 * cfg.ssm_state
    return d_in, nh, conv_ch


def init(key, cfg, name: str = "mamba"):
    d = cfg.d_model
    d_in, nh, conv_ch = _dims(cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    k = fold_in_name(key, name)
    ks = jax.random.split(k, 4)
    proj_out = 2 * d_in + 2 * cfg.ssm_state + nh  # x, z, B, C, dt
    params = {
        "in_proj": jax.random.normal(ks[0], (d, proj_out), dtype) * d**-0.5,
        "conv_w": jax.random.normal(ks[1], (cfg.ssm_conv_width, conv_ch), dtype) * 0.1,
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "dt_bias": jnp.zeros((nh,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "out_proj": jax.random.normal(ks[2], (d_in, d), dtype) * d_in**-0.5,
        "norm_scale": jnp.zeros((d_in,), dtype),
    }
    axes = {
        "in_proj": ("embed", "ssm_inner"),
        "conv_w": ("conv_width", "ssm_inner"),
        "conv_b": ("ssm_inner",),
        "dt_bias": ("ssm_heads",),
        "A_log": ("ssm_heads",),
        "D": ("ssm_heads",),
        "out_proj": ("ssm_inner", "embed"),
        "norm_scale": ("ssm_inner",),
    }
    return params, axes


def init_cache(cfg, batch: int, dtype):
    d_in, nh, conv_ch = _dims(cfg)
    return {
        "ssm": jnp.zeros((batch, nh, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_ch), dtype),
    }


CACHE_AXES = {
    "ssm": ("batch", "ssm_heads", "ssm_state", None),
    "conv": ("batch", None, "ssm_inner"),
}


def _split_proj(proj, cfg, d_in, nh):
    x = proj[..., :d_in]
    z = proj[..., d_in : 2 * d_in]
    bmat = proj[..., 2 * d_in : 2 * d_in + cfg.ssm_state]
    cmat = proj[..., 2 * d_in + cfg.ssm_state : 2 * d_in + 2 * cfg.ssm_state]
    dt = proj[..., 2 * d_in + 2 * cfg.ssm_state :]
    return x, z, bmat, cmat, dt


def _gated_norm(params, y, z, eps):
    """Mamba2's gated RMSNorm (``norm_before_gate=False``): the gate first,
    then the norm over all d_inner channels (one group), then the gain."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    out = g * (var + eps) ** -0.5 * (1.0 + params["norm_scale"].astype(jnp.float32))
    return out.astype(y.dtype)


def ssd(x, dt, a, bm, cm, chunk: int, initial_state=None):
    """The state-space model ``h_t = exp(dt_t a) h_{t-1} + dt_t B_t x_t^T``,
    ``y_t = C_t h_t``, in Mamba2's chunked dual form: one scan over chunks of
    ``chunk`` tokens that carries the state; inside a chunk the masked
    quadratic form ``(L o C B^T) (dt x)`` with ``L[i, j] = exp(sum_{j<t<=i}
    dt_t a)``, plus the entering state read through ``C``. Each chunk is
    recomputed in the backward, so the gradient keeps one chunk's (Q, Q, H)
    decay and the states between chunks. A length that ``chunk`` does not
    divide is padded with ``dt = 0`` tokens, which neither decay nor feed the
    state. Float32 throughout, products at full precision. x (B,S,H,P), dt
    (B,S,H), a (H,), bm and cm (B,S,N), initial_state (B,H,N,P) or None for
    zeros; returns y (B,S,H,P) and the final state, both float32."""
    b, s, h, p = x.shape
    n = bm.shape[-1]
    chunk = min(chunk, s)
    nc = -(-s // chunk)
    mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)

    def chunks(t):  # (B,S,...) -> (nc,B,Q,...), zero-padded at the end
        t = jnp.pad(t.astype(jnp.float32), [(0, 0), (0, nc * chunk - s)] + [(0, 0)] * (t.ndim - 2))
        return jnp.moveaxis(t.reshape((b, nc, chunk) + t.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None]

    @jax.checkpoint
    def step(state, xs):
        xc, dtc, bc, cc = xs                     # (B,Q,H,P) (B,Q,H) (B,Q,N) (B,Q,N)
        xdt = xc * dtc[..., None]
        cum = jnp.cumsum(dtc * a, axis=1)        # (B,Q,H): log decay since the chunk began
        decay = jnp.exp(jnp.where(causal, cum[:, :, None] - cum[:, None], -jnp.inf))
        y = mm("bij,bijh,bjhp->bihp", mm("bin,bjn->bij", cc, bc), decay, xdt)
        y = y + mm("bin,bhnp->bihp", cc, state) * jnp.exp(cum)[..., None]
        tail = jnp.exp(cum[:, -1:] - cum)[..., None]  # decay from each token to the chunk's end
        state = state * jnp.exp(cum[:, -1])[:, :, None, None] + mm("bjn,bjhp->bhnp", bc, xdt * tail)
        return state, y

    state = (jnp.zeros((b, h, n, p), jnp.float32) if initial_state is None
             else initial_state.astype(jnp.float32))
    state, y = jax.lax.scan(step, state, (chunks(x), chunks(dt), chunks(bm), chunks(cm)))
    return jnp.moveaxis(y, 0, 1).reshape(b, nc * chunk, h, p)[:, :s], state


def apply(params, x, cfg, *, cache=None, cache_index=None):
    """x: (B,S,d). Returns (y, new_cache)."""
    b, s, d = x.shape
    d_in, nh, conv_ch = _dims(cfg)
    hd = cfg.ssm_head_dim
    dtype = x.dtype
    proj = jnp.einsum("bsd,dp->bsp", x, params["in_proj"].astype(dtype))
    proj = constrain(proj, ("batch", "seq", "ssm_inner"))
    xin, z, bmat, cmat, dt = _split_proj(proj, cfg, d_in, nh)
    conv_in = jnp.concatenate([xin, bmat, cmat], axis=-1)  # (B,S,conv_ch)

    decode = cache is not None and s == 1 and cache_index is not None
    new_cache = cache
    w = params["conv_w"].astype(dtype)  # (W, conv_ch)
    if decode:
        window = jnp.concatenate([cache["conv"], conv_in], axis=1)  # (B,W,ch)
        # same f32 conv op as the prefill path below (not a bf16 einsum), so
        # a token produces bit-identical activations whether it arrives via
        # prefill or single-token decode — the paged engine feeds tail prompt
        # tokens through decode ticks and relies on this equivalence
        conv_out = jax.lax.conv_general_dilated(
            window.astype(jnp.float32),
            w.astype(jnp.float32)[:, None, :],
            window_strides=(1,),
            padding=[(0, 0)],
            dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=conv_ch,
        ).astype(dtype) + params["conv_b"].astype(dtype)
        new_conv = window[:, 1:, :]
    else:
        # causal depthwise conv, feature_group per channel. The left context
        # is the cache's rolling window when one is present (zeros on a fresh
        # cache — identical to plain left-padding — and the previous chunk's
        # tail during chunked prefill) so prefill can resume mid-sequence.
        left = (
            cache["conv"] if cache is not None
            else jnp.zeros((b, cfg.ssm_conv_width - 1, conv_ch), dtype)
        )
        windowed = jnp.concatenate([left.astype(dtype), conv_in], axis=1)
        conv_out = jax.lax.conv_general_dilated(
            windowed.astype(jnp.float32),
            w.astype(jnp.float32)[:, None, :],  # (W, 1, ch) as (spatial, in/group, out)
            window_strides=(1,),
            padding=[(0, 0)],
            dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=conv_ch,
        ).astype(dtype) + params["conv_b"].astype(dtype)
        new_conv = (
            windowed[:, -(cfg.ssm_conv_width - 1) :, :] if cache is not None else None
        )
    conv_out = jax.nn.silu(conv_out.astype(jnp.float32)).astype(dtype)
    xin = conv_out[..., :d_in]
    bmat = conv_out[..., d_in : d_in + cfg.ssm_state]
    cmat = conv_out[..., d_in + cfg.ssm_state :]

    dtp = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))  # (B,S,H)
    a = -jnp.exp(params["A_log"])  # (H,) negative

    xh = xin.reshape(b, s, nh, hd)
    if decode:
        # linear-attention mapping: q=C, k=B (shared over heads), v=dt*x
        q = jnp.broadcast_to(cmat[:, :, None, :], (b, s, nh, cfg.ssm_state))
        kk = jnp.broadcast_to(bmat[:, :, None, :], (b, s, nh, cfg.ssm_state))
        vv = (xh.astype(jnp.float32) * dtp[..., None]).astype(dtype)
        lw = jnp.broadcast_to((dtp * a)[..., None], (b, s, nh, cfg.ssm_state))  # log a_t = dt A
        y1, new_state = gla_step(
            cache["ssm"], q[:, 0], kk[:, 0], vv[:, 0], lw[:, 0], include_current=True
        )
        y = y1[:, None]  # (B,1,H,hd)
        new_cache = {"ssm": new_state, "conv": new_conv}
    else:
        # a whole sequence (training) or a prefill chunk, which carries the
        # SSM state in from the cache and continues exactly where the last
        # chunk ended
        with jax.named_scope("ssm_scan"):
            y, final_state = ssd(xh, dtp, a, bmat, cmat, SSD_CHUNK,
                                 None if cache is None else cache["ssm"])
        y = y.astype(dtype)
        if cache is not None:
            new_cache = {"ssm": final_state, "conv": new_conv}
    y = y + xh * params["D"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(b, s, d_in)
    y = _gated_norm(params, y, z, cfg.norm_eps)
    out = jnp.einsum("bsi,id->bsd", y.astype(dtype), params["out_proj"].astype(dtype))
    out_axes = (
        ("batch", "seq_sp", "embed")
        if getattr(cfg, "tp_reduce_scatter", False)
        else ("batch", "seq", "embed")
    )
    return constrain(out, out_axes), new_cache
