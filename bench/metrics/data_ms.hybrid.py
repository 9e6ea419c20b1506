"""Mean time of an update's data phase (``train.data``), in ms: the reader
of ``data_ms.sebs``, whose spans reshape mode records alike."""
from pathlib import Path

from bench import common

read = common.load_module(Path(__file__).with_name("data_ms.sebs.py")).read
