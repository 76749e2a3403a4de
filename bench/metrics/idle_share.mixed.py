"""Device: the share of the traced window in which no operation ran on
the chip, in %."""

from benchlib import layers


def read(run):
    return layers.idle_share(run)
