"""Next-token cross-entropy with router-aux and optional z-loss.

The label at position t is token t+1 (the last position is masked), so the
model input keeps the exact assigned (B, seq_len) shape for the dry-runs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def lm_loss(model, params, batch, *, z_loss: float = 0.0, aux_weight: float = 0.01):
    logits, aux = model.forward(params, batch)  # (B,S,V) f32
    with jax.named_scope("head"):
        return _cross_entropy(logits, aux, batch["tokens"], z_loss, aux_weight)


def _cross_entropy(logits, aux, tokens, z_loss, aux_weight):
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
    mask = jnp.concatenate(
        [jnp.ones_like(tokens[:, 1:], jnp.float32), jnp.zeros_like(tokens[:, -1:], jnp.float32)],
        axis=1,
    )
    lse = jax.nn.logsumexp(logits, axis=-1)
    # vocab-sharding-safe label pick: broadcast-compare-select fuses into the
    # reduction under GSPMD (take_along_axis would gather the full vocab dim
    # onto every device — measured 27 GB/device on whisper train_4k).
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    true_logit = jnp.sum(
        jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1
    )
    nll = (lse - true_logit) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = nll.sum() / denom
    if z_loss:
        loss = loss + z_loss * (jnp.square(lse) * mask).sum() / denom
    total = loss + aux_weight * aux
    metrics = {"loss": loss, "aux": aux, "tokens": denom}
    return total, metrics
