"""Token embedding and logit head with vocab padding (so the vocabulary
dimension shards cleanly over the 16-way ``model`` axis, e.g. whisper's
51865 → 51968), the config's embedding multiplier and logit divisor, and
gemma-style final-logit soft-capping."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sharding import constrain
from repro.utils.prng import fold_in_name


def init(key, cfg, name: str = "embed"):
    v, d = cfg.padded_vocab, cfg.d_model
    dtype = jnp.dtype(cfg.param_dtype)
    k = fold_in_name(key, name)
    params = {"table": jax.random.normal(k, (v, d), dtype) * d**-0.5}
    axes = {"table": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        params["unembed"] = jax.random.normal(fold_in_name(k, "un"), (d, v), dtype) * d**-0.5
        axes["unembed"] = ("embed", "vocab")
    return params, axes


def embed(params, tokens, cfg):
    x = jnp.take(params["table"], tokens, axis=0).astype(jnp.dtype(cfg.compute_dtype))
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, x.dtype)
    return constrain(x, ("batch", "seq", "embed"))


def logits(params, x, cfg):
    if cfg.tie_embeddings:
        w = params["table"].astype(x.dtype).T  # (d, V)
    else:
        w = params["unembed"].astype(x.dtype)
    out = jnp.einsum("bsd,dv->bsv", x, w).astype(jnp.float32)
    if cfg.logit_scale != 1.0:
        out = out / cfg.logit_scale
    cap = cfg.final_logit_softcap
    if cap is not None:
        out = cap * jnp.tanh(out / cap)
    return constrain(out, ("batch", "seq", "vocab"))
