"""``bench/flops.py`` against counts made by hand at qwen2.5-3b shapes."""
from bench import common, flops


def _cfg(**over):
    return dict(common.load_config("qwen2.5-3b-train4l"), **over)


def test_parameter_count_is_the_published_3_09b():
    # per layer: q 2048*16*128 + k,v 2*2048*2*128 + o 16*128*2048 + mlp 3*2048*11008
    # = 77,070,336 matrices + 2,560 biases + 4,096 norm gains; 36 layers,
    # the tied 151,936 x 2048 embedding once, the final norm
    c = _cfg(num_hidden_layers=36)
    assert flops.matmul_params_per_layer(c) == 77_070_336
    assert flops.params_total(c) == 36 * 77_076_992 + 311_164_928 + 2048 == 3_085_938_688


def test_train_flops_per_token_at_4_layers_and_seq_512():
    # 3 x (2 x (4 x 77,070,336 + 311,164,928) + 4 layers x 4 x 16 x 128 x 256.5)
    assert flops.train_flops_per_token(_cfg(), 512) == 3 * (
        1_238_892_544 + 32_768 * 256.5) == 3_741_892_608


def test_forward_flops_grow_with_context_by_attention_alone():
    c = _cfg()
    assert flops.forward_flops_per_token(c, 300) - flops.forward_flops_per_token(c, 100) == \
        4 * 4 * 16 * 128 * 200
