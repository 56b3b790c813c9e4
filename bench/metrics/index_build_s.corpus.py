"""Index build, seconds per pass."""
import layers


def read(run):
    return layers.per_pass(run, ['build_index'], exclude_under=())
