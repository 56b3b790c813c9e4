"""Engine prologue (engine chunk gather, tile and chunk masks), ms per pass."""
import layers


def read(run):
    return layers.per_pass(run, ['prologue'], scale=1e3)
