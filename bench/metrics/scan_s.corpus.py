"""Tile scan, seconds per pass."""
import layers


def read(run):
    return layers.per_pass(run, ['scan'], exclude_under=())
