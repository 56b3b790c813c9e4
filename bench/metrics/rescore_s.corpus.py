"""Exact rescore, seconds per pass."""
import layers


def read(run):
    return layers.per_pass(run, ['rescore'], exclude_under=())
