"""granite-4.0-h-micro [hybrid]: 40L d_model=2048; per period of 10 layers
five Mamba2 layers, one NoPE GQA attention layer (32H, kv 8, head dim 64),
four Mamba2 layers; every layer a SwiGLU MLP of 8192; Mamba2 d_inner 4096,
64 heads x 64, d_state 128, one group, conv 4 with bias; embeddings x12,
branches x0.22 before each residual add, softmax scale 1/64, logits / 8;
vocab 100352, tied
[hf:ibm-granite/granite-4.0-h-micro/blob/main/config.json].

The period is laid out as segments of one block each, so that every layer
is its own remat unit: a 10-block scan body would recompute, and hold for
its backward, ten layers' activations at once.
"""
from repro.configs.base import BlockSpec, ModelConfig, SegmentSpec

_MAMBA = BlockSpec(mixer="mamba2", ffn="dense")
_ATTN = BlockSpec(mixer="attn", ffn="dense")


def _layers(n: int):
    """Segments of the first ``n`` layers: ``layer_types`` repeats
    (mamba x5, attention, mamba x4), so attention sits at 5, 15, 25, 35."""
    kinds = ["attn" if i % 10 == 5 else "mamba2" for i in range(n)]
    segs = []
    for kind in kinds:
        if segs and segs[-1][0] == kind:
            segs[-1][1] += 1
        else:
            segs.append([kind, 1])
    return tuple(SegmentSpec(body=(_ATTN if k == "attn" else _MAMBA,), repeat=r)
                 for k, r in segs)


CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    cite="hf:ibm-granite/granite-4.0-h-micro/blob/main/config.json",
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100352,
    rope=False,
    attn_scale=0.015625,
    embed_scale=12.0,
    residual_scale=0.22,
    logit_scale=8.0,
    norm_eps=1e-5,
    ssm_state=128,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_conv_width=4,
    segments=_layers(40),
)


def smoke() -> ModelConfig:
    """One whole period (10 layers) at CPU-test widths."""
    return CONFIG.replace(
        name="granite-4.0-h-micro-smoke",
        d_model=128, num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=512, ssm_state=16, ssm_head_dim=32,
        segments=_layers(10),
    )
