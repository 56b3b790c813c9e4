"""Exact rescore of the pairs near the threshold, ms per pass."""
import layers


def read(run):
    return layers.per_pass(run, ['rescore'], scale=1e3)
