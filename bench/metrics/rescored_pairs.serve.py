"""Pairs rescored exactly per pass (the engine's own count)."""
import layers


def read(run):
    return layers.stat_mean(run, 'rescored_pairs')
