"""Decoder blocks and scanned segments.

A *block* is (pre-norm → mixer → residual, pre-norm → ffn → residual), each
branch times ``cfg.residual_scale`` before its add. A
*segment* is ``repeat`` iterations of a tuple of blocks (the "body"),
executed with ``lax.scan`` over weights stacked on a leading ``layers``
axis — HLO stays O(1) in depth, which keeps the 95-layer deepseek-67b and
54-layer zamba2 dry-runs fast to lower and compile.

zamba2's weight-tied shared attention block is a closure constant inside the
scan body (weights stored once → tied), while its per-invocation KV cache is
scanned like every other cache leaf.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import BlockSpec, ModelConfig, SegmentSpec
from repro.models.layers import attention, mamba2, mlp, moe, norm, rwkv6
from repro.sharding import constrain
from repro.utils.prng import fold_in_name
from repro.utils.tree import tree_stack

# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------


def _branch(cfg: ModelConfig, y):
    """A mixer or FFN output as it joins the residual stream."""
    if cfg.residual_scale == 1.0:
        return y
    return y * jnp.asarray(cfg.residual_scale, y.dtype)


def init_block(key, cfg: ModelConfig, spec: BlockSpec, name: str):
    k = fold_in_name(key, name)
    params, axes = {}, {}

    n1, a1 = norm.init(cfg.d_model, jnp.dtype(cfg.param_dtype))
    params["norm1"], axes["norm1"] = n1, a1

    if spec.mixer in ("attn", "swa"):
        p, a = attention.init(k, cfg, name=f"{name}/attn")
        params["attn"], axes["attn"] = p, a
    elif spec.mixer == "cross_attn_block":
        p, a = attention.init(k, cfg, name=f"{name}/self_attn")
        params["attn"], axes["attn"] = p, a
        nx, ax = norm.init(cfg.d_model, jnp.dtype(cfg.param_dtype))
        params["norm_cross"], axes["norm_cross"] = nx, ax
        px, acx = attention.init(k, cfg, name=f"{name}/cross_attn", cross=True)
        params["cross_attn"], axes["cross_attn"] = px, acx
    elif spec.mixer == "mamba2":
        p, a = mamba2.init(k, cfg, name=f"{name}/mamba")
        params["mamba"], axes["mamba"] = p, a
    elif spec.mixer == "rwkv6":
        p, a = rwkv6.init_time_mix(k, cfg, name=f"{name}/tmix")
        params["tmix"], axes["tmix"] = p, a
    else:  # pragma: no cover
        raise ValueError(spec.mixer)

    if spec.ffn != "none":
        n2, a2 = norm.init(cfg.d_model, jnp.dtype(cfg.param_dtype))
        params["norm2"], axes["norm2"] = n2, a2
    if spec.ffn == "dense":
        p, a = mlp.init(k, cfg, name=f"{name}/mlp")
        params["mlp"], axes["mlp"] = p, a
    elif spec.ffn == "moe":
        p, a = moe.init(k, cfg, name=f"{name}/moe")
        params["moe"], axes["moe"] = p, a
        if cfg.moe_dense_residual:
            p2, a2 = mlp.init(k, cfg, name=f"{name}/residual_mlp")
            params["mlp"], axes["mlp"] = p2, a2
    elif spec.ffn == "rwkv_cmix":
        p, a = rwkv6.init_channel_mix(k, cfg, name=f"{name}/cmix")
        params["cmix"], axes["cmix"] = p, a
    return params, axes


def init_block_cache(cfg: ModelConfig, spec: BlockSpec, batch: int, cache_len: int, dtype):
    cache = {}
    if spec.mixer in ("attn", "swa", "cross_attn_block"):
        cache["attn"] = attention.init_cache(cfg, batch, cache_len, dtype)
    elif spec.mixer == "mamba2":
        cache["mamba"] = mamba2.init_cache(cfg, batch, dtype)
    elif spec.mixer == "rwkv6":
        cache["rwkv"] = rwkv6.init_cache(cfg, batch, dtype)
    return cache


def init_block_cache_paged(
    cfg: ModelConfig, spec: BlockSpec, num_pages: int, page_size: int,
    state_batch: int, dtype,
):
    """Paged layout: attention KV lives in the shared page pool
    ((num_pages, hkv, page_size, hd) leaves, one page id spanning every layer);
    O(1) recurrent state (SSM/conv/RWKV) stays per-slot dense at
    ``state_batch`` rows."""
    cache = {}
    if spec.mixer in ("attn", "swa", "cross_attn_block"):
        cache["attn"] = attention.init_paged_cache(cfg, num_pages, page_size, dtype)
    elif spec.mixer == "mamba2":
        cache["mamba"] = mamba2.init_cache(cfg, state_batch, dtype)
    elif spec.mixer == "rwkv6":
        cache["rwkv"] = rwkv6.init_cache(cfg, state_batch, dtype)
    return cache


def block_cache_axes(spec: BlockSpec):
    axes = {}
    if spec.mixer in ("attn", "swa", "cross_attn_block"):
        axes["attn"] = dict(attention.CACHE_AXES)
    elif spec.mixer == "mamba2":
        axes["mamba"] = dict(mamba2.CACHE_AXES)
    elif spec.mixer == "rwkv6":
        axes["rwkv"] = dict(rwkv6.CACHE_AXES)
    return axes


def apply_block(
    params,
    x,
    cfg: ModelConfig,
    spec: BlockSpec,
    *,
    positions,
    cache=None,
    cache_index=None,
    memory=None,
    causal: bool = True,
    page_table=None,
):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = dict(cache) if cache is not None else None
    decode = cache is not None and x.shape[1] == 1 and cache_index is not None

    # named scopes label the block's ops (op_name) in a device profile
    attn_like = spec.mixer in ("attn", "swa", "cross_attn_block")
    with jax.named_scope("attention" if attn_like else spec.mixer):
        h = norm.apply(params["norm1"], x, cfg.norm_eps)
        if attn_like:
            window = None
            if spec.mixer == "swa":
                window = spec.sliding_window or cfg.sliding_window
            y, attn_cache = attention.apply(
                params["attn"], h, cfg,
                positions=positions, causal=causal, sliding_window=window,
                cache=None if cache is None else cache.get("attn"),
                cache_index=cache_index, page_table=page_table,
            )
            if new_cache is not None and attn_cache is not None:
                new_cache["attn"] = attn_cache
            y = checkpoint_name(y, "mixer_out")
            x = x + _branch(cfg, y)
            if spec.mixer == "cross_attn_block" and memory is not None:
                hx = norm.apply(params["norm_cross"], x, cfg.norm_eps)
                yx, _ = attention.apply(
                    params["cross_attn"], hx, cfg, positions=positions,
                    causal=False, memory=memory,
                )
                x = x + _branch(cfg, yx)
        elif spec.mixer == "mamba2":
            y, mcache = mamba2.apply(
                params["mamba"], h, cfg,
                cache=None if cache is None else cache.get("mamba"),
                cache_index=cache_index,
            )
            if new_cache is not None and mcache is not None:
                new_cache["mamba"] = mcache
            y = checkpoint_name(y, "mixer_out")
            x = x + _branch(cfg, y)
        elif spec.mixer == "rwkv6":
            rc = None if cache is None else cache.get("rwkv")
            y, wkv, shift_t = rwkv6.apply_time_mix(params["tmix"], h, cfg, cache=rc, decode=decode)
            if new_cache is not None:
                new_cache["rwkv"] = dict(new_cache.get("rwkv", {}))
                new_cache["rwkv"].update({"wkv": wkv, "shift_t": shift_t})
            x = x + _branch(cfg, y)

    if spec.ffn == "none":
        return x, new_cache, aux
    with jax.named_scope("mlp" if spec.ffn == "dense" else spec.ffn):
        h = norm.apply(params["norm2"], x, cfg.norm_eps)
        if spec.ffn == "dense":
            x = x + _branch(cfg, checkpoint_name(mlp.apply(params["mlp"], h, cfg), "ffn_out"))
        elif spec.ffn == "moe":
            y, moe_aux = moe.apply(params["moe"], h, cfg)
            aux = aux + moe_aux
            if cfg.moe_dense_residual:
                y = y + mlp.apply(params["mlp"], h, cfg)
            x = x + _branch(cfg, checkpoint_name(y, "ffn_out"))
        elif spec.ffn == "rwkv_cmix":
            rc = None if cache is None else cache.get("rwkv")
            y, shift_c = rwkv6.apply_channel_mix(params["cmix"], h, cfg, cache=rc)
            if new_cache is not None:
                new_cache["rwkv"] = dict(new_cache.get("rwkv", {}))
                new_cache["rwkv"]["shift_c"] = shift_c
            x = x + _branch(cfg, y)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# scanned segment
# ---------------------------------------------------------------------------

SHARED_SPEC = BlockSpec(mixer="attn", ffn="dense")

# activation-checkpoint policies selectable per config (perf hillclimb knob)
REMAT_POLICIES = {
    "nothing_saveable": lambda: jax.checkpoint_policies.nothing_saveable,
    "dots_saveable": lambda: jax.checkpoint_policies.dots_saveable,
    "dots_no_batch": lambda: jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    # save the (cheap, seq-parallel-sharded) mixer/FFN residual branches so
    # the backward pass does not replay the forward's weight all-gathers and
    # TP collectives (§Perf hillclimb iteration)
    "save_block_outputs": lambda: jax.checkpoint_policies.save_only_these_names(
        "mixer_out", "ffn_out"
    ),
}


def init_segment(key, cfg: ModelConfig, seg: SegmentSpec, name: str):
    """Returns (params, axes). Body params stacked over the repeat axis."""
    params, axes = {}, {}
    for bi, spec in enumerate(seg.body):
        reps = []
        for r in range(seg.repeat):
            p, a = init_block(key, cfg, spec, name=f"{name}/rep{r}/b{bi}")
            reps.append(p)
        params[f"b{bi}"] = tree_stack(reps)
        axes[f"b{bi}"] = jax.tree.map(
            lambda ax: ("layers",) + ax,
            a,
            is_leaf=lambda t: isinstance(t, tuple) and all(isinstance(e, (str, type(None))) for e in t),
        )
    if seg.shared_attn:
        p, a = init_block(key, cfg, SHARED_SPEC, name=f"{name}/shared")
        params["shared"] = p
        axes["shared"] = a
    return params, axes


def init_segment_cache(cfg: ModelConfig, seg: SegmentSpec, batch: int, cache_len: int, dtype):
    cache = {}
    for bi, spec in enumerate(seg.body):
        c = init_block_cache(cfg, spec, batch, cache_len, dtype)
        if c:
            cache[f"b{bi}"] = tree_stack([c] * seg.repeat)
    if seg.shared_attn:
        c = init_block_cache(cfg, SHARED_SPEC, batch, cache_len, dtype)
        cache["shared"] = tree_stack([c] * seg.repeat)
    return cache


def init_segment_cache_paged(
    cfg: ModelConfig, seg: SegmentSpec, num_pages: int, page_size: int,
    state_batch: int, dtype,
):
    cache = {}
    for bi, spec in enumerate(seg.body):
        c = init_block_cache_paged(cfg, spec, num_pages, page_size, state_batch, dtype)
        if c:
            cache[f"b{bi}"] = tree_stack([c] * seg.repeat)
    if seg.shared_attn:
        c = init_block_cache_paged(cfg, SHARED_SPEC, num_pages, page_size, state_batch, dtype)
        cache["shared"] = tree_stack([c] * seg.repeat)
    return cache


def segment_cache_axes(seg: SegmentSpec):
    axes = {}

    def prefix(a):
        return jax.tree.map(
            lambda ax: ("layers",) + ax,
            a,
            is_leaf=lambda t: isinstance(t, tuple) and all(isinstance(e, (str, type(None))) for e in t),
        )

    for bi, spec in enumerate(seg.body):
        a = block_cache_axes(spec)
        if a:
            axes[f"b{bi}"] = prefix(a)
    if seg.shared_attn:
        axes["shared"] = prefix(block_cache_axes(SHARED_SPEC))
    return axes


def apply_segment(
    params,
    x,
    cfg: ModelConfig,
    seg: SegmentSpec,
    *,
    positions,
    cache=None,
    cache_index=None,
    memory=None,
    causal: bool = True,
    page_table=None,
):
    """Scan the segment body over the repeat axis. Returns (x, new_cache, aux)."""
    shared = params.get("shared")

    def body(carry, xs):
        h, aux = carry
        layer_params, layer_cache = xs
        # sequence-parallel residual stream: the remat-saved carry is
        # (batch × model)-sharded; attention/MoE gather what they need.
        h = constrain(h, ("batch", "seq_sp", "embed"))
        new_layer_cache = {} if layer_cache is not None else None
        if shared is not None:
            y, c, a = apply_block(
                shared, h, cfg, SHARED_SPEC, positions=positions,
                cache=None if layer_cache is None else layer_cache.get("shared"),
                cache_index=cache_index, memory=memory, causal=causal,
                page_table=page_table,
            )
            h, aux = y, aux + a
            if new_layer_cache is not None and c is not None:
                new_layer_cache["shared"] = c
        for bi, spec in enumerate(seg.body):
            y, c, a = apply_block(
                layer_params[f"b{bi}"], h, cfg, spec, positions=positions,
                cache=None if layer_cache is None else layer_cache.get(f"b{bi}"),
                cache_index=cache_index, memory=memory, causal=causal,
                page_table=page_table,
            )
            h, aux = y, aux + a
            if new_layer_cache is not None and c is not None:
                new_layer_cache[f"b{bi}"] = c
        return (h, aux), new_layer_cache

    fn = (
        jax.checkpoint(body, policy=REMAT_POLICIES[cfg.remat_policy]())
        if cfg.remat
        else body
    )

    scan_params = {k: v for k, v in params.items() if k != "shared"}
    if cfg.scan_layers:
        (x, aux), new_cache = jax.lax.scan(
            fn, (x, jnp.zeros((), jnp.float32)), (scan_params, cache)
        )
        return x, new_cache, aux
    # unrolled path (roofline cost-extrapolation compiles)
    carry = (x, jnp.zeros((), jnp.float32))
    caches = []
    for r in range(seg.repeat):
        xs = (
            jax.tree.map(lambda v: v[r], scan_params),
            None if cache is None else jax.tree.map(lambda v: v[r], cache),
        )
        carry, c = fn(carry, xs)
        caches.append(c)
    x, aux = carry
    new_cache = tree_stack(caches) if cache is not None else None
    return x, new_cache, aux
