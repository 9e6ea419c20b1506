"""Mean extra time of the first update after each stage change, from ``train.update`` spans, in ms."""
from bench.readers import stage_switch_ms as read  # noqa: F401
