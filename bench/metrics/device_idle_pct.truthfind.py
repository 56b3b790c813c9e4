"""Percent of the traced window with no operation on the device."""
import layers


def read(run):
    return layers.idle_pct(run)
