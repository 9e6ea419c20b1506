"""``bench/flops_hybrid.py`` against counts made by hand at the
granite-4.0-h-micro-train10l shapes, and the readers of the two new cells."""
from types import SimpleNamespace

from bench import common, flops_hybrid


def _cfg(**over):
    return dict(common.load_config("granite-4.0-h-micro-train10l"), **over)


def test_layer_products_by_hand():
    # mamba: in_proj 2048 x (2*4096 + 2*128 + 64) + out_proj 4096 x 2048
    # + mlp 3 x 2048 x 8192; attention: q, o 2 x 2048 x 32 x 64, k, v
    # 2 x 2048 x 8 x 64, mlp as above
    assert flops_hybrid.mamba_matmul_params(_cfg()) == 17_432_576 + 8_388_608 + 50_331_648
    assert flops_hybrid.attention_matmul_params(_cfg()) == 8_388_608 + 2_097_152 + 50_331_648


def test_train_flops_per_token_at_seq_2048_by_hand():
    # forward: 9 x (2 x 76,152,832 + 4 x 128 x 64 x 64) mamba
    #        + 1 x (2 x 60,817,408 + 4 x 32 x 64 x 1024.5) attention
    #        + 2 x 2048 x 25,088 head
    fwd = 9 * (152_305_664 + 2_097_152) + (121_634_816 + 8_392_704) + 102_760_448
    assert sum(flops_hybrid.forward_flops_per_token(_cfg(), 1024.5).values()) == fwd
    assert flops_hybrid.train_flops_per_token(_cfg(), 2048) == 3 * fwd == 4_867_239_936


def test_mamba_layers_hold_most_of_the_forward():
    part = flops_hybrid.forward_flops_per_token(_cfg(), 1024.5)
    assert part["mamba"] / sum(part.values()) > 0.85


def test_readers_of_the_new_cells():
    spans = [{"name": "train.data", "dur": 0.002}, {"name": "train.wait", "dur": 0.5},
             {"name": "train.update", "dur": 0.51}, {"name": "train.after", "dur": 0.004}]
    run = SimpleNamespace(kind="train", config=_cfg(), traffic={"seq": 2048}, chips=1,
                          tokens_per_s=1000.0, peaks={"bf16_flops": 197e12}, spans=spans)
    mfu = common.load_reader("mfu.train.hybrid")(run)
    assert abs(mfu - 100 * 4_867_239_936 * 1000.0 / 197e12) < 1e-9
    assert common.load_reader("mfu.train.hybrid")(SimpleNamespace(kind="serve")) is None
    assert abs(common.load_reader("data_ms.hybrid")(run) - 2.0) < 1e-9
    assert abs(common.load_reader("update_host_ms.hybrid")(run) - 14.0) < 1e-9
