"""device_idle_share: share of the traced window in which no operation
ran on the device (1 - union of the op intervals / window), in %."""

from harness.readers import idle_share


def read(run):
    return idle_share(run)
