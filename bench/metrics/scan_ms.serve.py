"""Tile scan (chunk staging and the kernel launches, to the host grids), ms per pass."""
import layers


def read(run):
    return layers.per_pass(run, ['scan'], scale=1e3)
