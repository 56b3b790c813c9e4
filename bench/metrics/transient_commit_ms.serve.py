"""Transient commit and rollback of the batch's rows in the committed index, ms per pass."""
import layers


def read(run):
    return layers.per_pass(run, ['index_commit', 'index_rollback'], scale=1e3)
