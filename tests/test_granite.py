"""granite-4.0-h-micro against its plain float32 reference
(``bench/reference/granite_hybrid.py``) on seeded random weights, at the
smoke size (one whole period: mamba x5, attention, mamba x4) with float32
compute: the loss and every leaf's gradient; the training path's chunked
SSM against the token recurrence; the Mamba2 gated norm's order; NoPE and
the four multipliers, each against its formula; and gemma2's forward
unchanged by the embedding multiplier taking the name test's place.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))  # bench/

from bench import program_hybrid  # noqa: E402
from bench.reference import granite_hybrid as ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import blocks, build_model  # noqa: E402
from repro.models.layers import attention, embedding, mamba2, mlp, norm  # noqa: E402
from repro.train.loss import lm_loss  # noqa: E402

SEQ = 128  # two of the program's 64-token scan checkpoints, eight reference chunks


def _smoke_file(**over) -> dict:
    """The smoke size as a configuration file (published key names)."""
    cfg = get_config("granite-4.0-h-micro", "smoke")
    full = json.loads((ROOT / "bench/configs/granite-4.0-h-micro-train10l.json").read_text())
    c = dict(full, hidden_size=cfg.d_model, num_attention_heads=cfg.num_heads,
             num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
             intermediate_size=cfg.d_ff, shared_intermediate_size=cfg.d_ff,
             vocab_size=cfg.vocab_size, num_hidden_layers=cfg.num_layers,
             layer_types=full["layer_types"][:cfg.num_layers],
             mamba_n_heads=cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim,
             mamba_d_head=cfg.ssm_head_dim, mamba_d_state=cfg.ssm_state, mamba_chunk_size=16,
             program_variant="smoke", reduced=[], compute_dtype="float32")
    return dict(c, **over)


@pytest.fixture(scope="module")
def pair():
    c = _smoke_file()
    model = program_hybrid.build(c)
    params = program_hybrid.program_weights(model, c, jax.random.key(3))
    w = ref.stacked_weights(jax.random.key(3), c, jnp.float32)
    tokens = jax.random.randint(jax.random.key(4), (2, SEQ), 0, c["vocab_size"])
    return c, model, params, w, tokens


def test_loss_and_every_gradient_match_the_reference(pair):
    c, model, params, w, tokens = pair
    (loss, _), g = jax.value_and_grad(
        lambda p: lm_loss(model, p, {"tokens": tokens}), has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        loss_r, g_r = jax.value_and_grad(ref.lm_loss)(w, tokens, c)
    # the SSM in chunks of 64 (program) and of 16 (reference), both in
    # float32: 3.8e-6 of a leaf's norm at worst, 7.6e-8 on the loss; the
    # bounds leave five and ten times that
    assert abs(float(loss) - float(loss_r)) <= 1e-6 * abs(float(loss_r))
    lmap = program_hybrid.leaf_map(model)
    want = program_hybrid.to_program(g_r, lmap, gains=False)
    for path, got in jax.tree_util.tree_leaves_with_path(g):
        exp = want
        for k in path:
            exp = exp[k.key]
        err = float(jnp.linalg.norm(got - exp) / jnp.linalg.norm(exp))
        assert err <= 2e-5, (jax.tree_util.keystr(path), err)


def _ssm_inputs(b, s, h, p, n, key=7):
    ks = jax.random.split(jax.random.key(key), 6)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.8))
    bm, cm = jax.random.normal(ks[3], (b, s, n)), jax.random.normal(ks[4], (b, s, n))
    h0 = jax.random.normal(ks[5], (b, h, n, p))
    return x, dt, a, bm, cm, h0


def _token_recurrence(x, dt, a, bm, cm, h0):
    """The SSM one token at a time (``gla_scan``, which rwkv6 runs): an
    algorithm independent of the chunked dual form."""
    from repro.models.layers.linear_attention import gla_scan

    b, s, h, _ = x.shape
    n = bm.shape[-1]
    over_heads = lambda t: jnp.broadcast_to(t[:, :, None], (b, s, h, n))
    return gla_scan(over_heads(cm), over_heads(bm), x * dt[..., None],
                    jnp.broadcast_to((dt * a)[..., None], (b, s, h, n)), include_current=True,
                    initial_state=h0)


@pytest.mark.parametrize("s,chunk", [(128, 32), (100, 32), (12, 256)])
def test_chunked_ssd_is_the_token_recurrence(s, chunk):
    """The dual form (``mamba2.ssd``) that training and prefill run, against
    the token recurrence, from a given state, in float32: whole chunks, a
    length that the chunk does not divide (padded), and one short chunk."""
    x, dt, a, bm, cm, h0 = _ssm_inputs(2, s, 4, 8, 16)
    want, want_h = _token_recurrence(x, dt, a, bm, cm, h0)
    got, got_h = mamba2.ssd(x, dt, a, bm, cm, chunk, h0)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got_h, want_h, rtol=1e-4, atol=1e-4)


def test_chunked_ssd_at_the_cells_shapes():
    """train-sebs-hybrid's SSM shapes (64 heads x 64, d_state 128, chunks of
    ``mamba_chunk_size`` 256, one row of 2,048 tokens) against the token
    recurrence: the cell's reference shares the chunked algorithm, so this
    is the check of the training path against an independent one."""
    cfg = get_config("granite-4.0-h-micro")
    h = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
    x, dt, a, bm, cm, _ = _ssm_inputs(1, 2048, h, cfg.ssm_head_dim, cfg.ssm_state, key=11)
    h0 = jnp.zeros((1, h, cfg.ssm_state, cfg.ssm_head_dim))
    want, want_h = _token_recurrence(x, dt, a, bm, cm, h0)
    got, got_h = mamba2.ssd(x, dt, a, bm, cm, mamba2.SSD_CHUNK)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * scale
    assert float(jnp.max(jnp.abs(got_h - want_h))) <= 1e-5 * float(jnp.max(jnp.abs(want_h)))


def test_gated_norm_gates_then_normalises():
    """Mamba2's gated RMSNorm with ``norm_before_gate=False``; the other
    order fails the same comparison."""
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    y = jax.random.normal(k1, (2, 5, 64))
    z = jax.random.normal(k2, (2, 5, 64)) * 2.0
    p = {"norm_scale": 0.1 * jax.random.normal(k3, (64,))}
    got = mamba2._gated_norm(p, y, z, 1e-5)
    g = y * jax.nn.silu(z)
    want = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5) * (1 + p["norm_scale"])
    old = (y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-5) * (1 + p["norm_scale"])
           * jax.nn.silu(z))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(old - want))) > 1e-1


def _attn_setup(**over):
    cfg = get_config("granite-4.0-h-micro", "smoke").replace(compute_dtype="float32", **over)
    p, _ = attention.init(jax.random.key(1), cfg)
    x = jax.random.normal(jax.random.key(2), (2, 12, cfg.d_model))
    return cfg, p, x


def _attn(cfg, p, x):
    return attention.apply(p, x, cfg, positions=jnp.arange(x.shape[1])[None])[0]


@pytest.mark.parametrize("scale", [None, 0.015625])
def test_nope_attention_is_the_plain_causal_softmax(scale):
    """``rope=False``: softmax(q.k * scale) over the keys at or before each
    query, no positional rotation; ``attn_scale`` None is 1/sqrt(head_dim)."""
    cfg, p, x = _attn_setup(attn_scale=scale)
    hd = cfg.resolved_head_dim
    group = cfg.num_heads // cfg.num_kv_heads
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    k = jnp.repeat(jnp.einsum("bsd,dnh->bsnh", x, p["wk"]), group, 2)
    v = jnp.repeat(jnp.einsum("bsd,dnh->bsnh", x, p["wv"]), group, 2)
    s = jnp.einsum("bqnh,bknh->bnqk", q, k) * (hd ** -0.5 if scale is None else scale)
    s = jnp.where(jnp.tril(jnp.ones((12, 12), bool)), s, -jnp.inf)
    o = jnp.einsum("bnqk,bknh->bqnh", jax.nn.softmax(s, -1), v)
    want = jnp.einsum("bsnh,nhd->bsd", o, p["wo"])
    np.testing.assert_allclose(_attn(cfg, p, x), want, rtol=2e-5, atol=2e-5)
    with_rope = _attn(cfg.replace(rope=True), p, x)
    assert float(jnp.max(jnp.abs(with_rope - want))) > 1e-2


def test_embedding_multiplier_and_logit_divisor():
    cfg = get_config("granite-4.0-h-micro", "smoke").replace(compute_dtype="float32")
    p, _ = embedding.init(jax.random.key(0), cfg)
    tokens = jnp.array([[3, 7, 511]])
    np.testing.assert_array_equal(embedding.embed(p, tokens, cfg), p["table"][tokens] * 12.0)
    x = jax.random.normal(jax.random.key(1), (1, 3, cfg.d_model))
    np.testing.assert_allclose(embedding.logits(p, x, cfg),
                               jnp.einsum("bsd,vd->bsv", x, p["table"]) / 8.0,
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("mixer", ["attn", "mamba2"])
def test_residual_multiplier_scales_both_branches(mixer):
    """``x + r f(norm(x))``, then ``+ r mlp(norm(.))``, with r = 0.22."""
    cfg = get_config("granite-4.0-h-micro", "smoke").replace(compute_dtype="float32")
    spec = blocks.BlockSpec(mixer=mixer, ffn="dense")
    p, _ = blocks.init_block(jax.random.key(0), cfg, spec, "b")
    p = jax.tree.map(lambda t: t + 0.05, p)  # gains away from 1
    x = jax.random.normal(jax.random.key(1), (2, 8, cfg.d_model))
    pos = jnp.arange(8)[None]
    got = blocks.apply_block(p, x, cfg, spec, positions=pos)[0]
    h = norm.apply(p["norm1"], x, cfg.norm_eps)
    y = (attention.apply(p["attn"], h, cfg, positions=pos)[0] if mixer == "attn"
         else mamba2.apply(p["mamba"], h, cfg)[0])
    x1 = x + 0.22 * y
    want = x1 + 0.22 * mlp.apply(p["mlp"], norm.apply(p["norm2"], x1, cfg.norm_eps), cfg)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_gemma2_forward_bit_identical_to_the_name_test(monkeypatch):
    """gemma2 scales its embeddings by sqrt(d_model) through ``embed_scale``
    now; its smoke forward equals the one under the old name test."""
    cfg = get_config("gemma2-9b", "smoke")
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)}
    new = model.forward(params, batch)[0]

    def by_name(p, tokens, c):
        x = jnp.take(p["table"], tokens, axis=0).astype(jnp.dtype(c.compute_dtype))
        if c.name.startswith("gemma"):
            x = x * jnp.asarray(c.d_model ** 0.5, x.dtype)
        return x

    monkeypatch.setattr(embedding, "embed", by_name)
    old = model.forward(params, batch)[0]
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
