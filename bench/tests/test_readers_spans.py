"""The readers of the trainer's phase spans (``update_host_ms.sebs``,
``data_ms.sebs``) on hand-made spans, and None where there is nothing to
read: another kind of run, or a trainer that records only ``train.update``."""
from types import SimpleNamespace as NS

import pytest

from bench import common

update_host_ms = common.load_reader("update_host_ms.sebs")
data_ms = common.load_reader("data_ms.sebs")


def _update(data, dispatch, wait, other, after, stage=0):
    """One update's spans in the order the tracer records them (as each
    closes); ``other`` is update time outside its three children."""
    return [
        {"name": "train.data", "dur": data},
        {"name": "train.dispatch", "dur": dispatch},
        {"name": "train.wait", "dur": wait},
        {"name": "train.update", "dur": data + dispatch + wait + other,
         "args": {"stage": stage}},
        {"name": "train.after", "dur": after},
    ]


def _run(spans, kind="train"):
    return NS(kind=kind, spans=spans)


def test_phase_readers():
    spans = (_update(.001, .002, .100, .0005, .004)
             + _update(.003, .002, .200, .0, .002, stage=1))
    run = _run(spans)
    # (update - wait) + after: (0.0035 + 0.004) and (0.005 + 0.002)
    assert update_host_ms(run) == pytest.approx(1e3 * (0.0075 + 0.007) / 2)
    assert data_ms(run) == pytest.approx(1e3 * (0.001 + 0.003) / 2)


def test_update_host_leaves_out_cut_updates():
    whole = _update(.001, .002, .100, .0, .004)
    # the ring dropped the head of the first update (its after survives),
    # and the window ended before the last update's after was recorded
    spans = whole[-1:] + whole + _update(.001, .001, .1, .0, .5)[:-1]
    assert update_host_ms(_run(spans)) == pytest.approx(1e3 * 0.007)


def test_nothing_to_read_gives_none():
    assert update_host_ms(_run([])) is None and data_ms(_run([])) is None
    # a trainer without phase spans: train.update alone
    only_updates = [{"name": "train.update", "dur": .1, "args": {"stage": 0}}] * 3
    assert update_host_ms(_run(only_updates)) is None
    assert data_ms(_run(only_updates)) is None
    serve = _run(_update(.001, .002, .1, .0, .004), kind="serve")
    assert update_host_ms(serve) is None and data_ms(serve) is None
