"""Device idle share of the traced window, in percent (device trace):
1 - union of operation intervals / window."""
from bench.metrics._device_idle import idle_percent


def read(ctx):
    return idle_percent(ctx)
