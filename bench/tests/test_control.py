"""The control (the reference at float8 products in the program's place) and
the half-batch fault, each put in the program's place by ``bench/control.py``,
come out as not correct through ``bench/run.py``'s own comparison at test size."""
import pytest

from bench import common, control, run

from conftest import DATA


@pytest.mark.parametrize("name", ["float8", "half_batch"])
def test_stand_in_is_not_correct(spec, name):
    stand_in = control.stand_ins(common.load_traffic("tiny-ladder", DATA))[name]
    main = lambda argv, stand_in: run.main(argv, require_tpu=False, spec=spec, data_dir=DATA,
                                           stand_in=stand_in)
    for seed in (1, 2):
        res = control.run_with(main, ["--workload", "train-sebs", "--seed", str(seed),
                                      "--seconds", "0.5", "--trace", "0"], stand_in)
        assert not res["correct"], res["checks"]
