"""Mean extra time of the first update after each stage change (in reshape
mode a new batch shape), from ``train.update`` spans, in ms."""
from bench.readers import stage_switch_ms as read  # noqa: F401
