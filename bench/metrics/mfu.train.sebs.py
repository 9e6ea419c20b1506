"""Model FLOP/s utilisation of the training window on one chip, in %."""
from bench.readers import train_mfu as read  # noqa: F401
