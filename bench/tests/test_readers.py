"""Per-layer readers on hand-made runs: the arithmetic, and None where a
run holds nothing to read."""
from types import SimpleNamespace as NS

import pytest

from bench import common, readers


def _train_run(stages_durs):
    spans = [{"name": "train.update", "dur": d, "args": {"stage": s}} for s, d in stages_durs]
    return NS(kind="train", spans=spans, tokens_per_s=10_000.0, chips=1,
              config=common.load_config("qwen2.5-3b-train4l"), traffic={"seq": 512},
              peaks={"bf16_flops": 197e12})


def test_train_readers():
    run = _train_run([(0, .3), (0, .1), (0, .1), (1, .4), (1, .2), (1, .2), (0, .5), (0, .1),
                      (1, .3)])
    # changes to a higher stage: stage 1 twice (0.4 and 0.3 vs median 0.2);
    # the return to stage 0 that starts a pass is no stage change
    assert readers.stage_switch_ms(run) == pytest.approx(1e3 * (0.2 + 0.1) / 2)
    assert readers.train_mfu(run) == pytest.approx(100 * 3_741_892_608 * 1e4 / 197e12)


def test_nothing_to_read_gives_none():
    assert readers.stage_switch_ms(_train_run([(0, .1), (0, .1)])) is None
    other = NS(kind="serve", spans=[], tokens_per_s=1.0)
    assert readers.stage_switch_ms(other) is None and readers.train_mfu(other) is None
