"""Host time per update in which the chip waits on the host, in ms: the
reader of ``update_host_ms.sebs``, whose spans reshape mode records alike."""
from pathlib import Path

from bench import common

read = common.load_module(Path(__file__).with_name("update_host_ms.sebs.py")).read
