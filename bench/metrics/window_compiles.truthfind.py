"""Compilations inside the measured window."""
import layers


def read(run):
    return layers.compiles(run)
