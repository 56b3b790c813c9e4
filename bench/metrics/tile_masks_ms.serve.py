"""Tile and chunk masks (``engine.tile_masks``), ms per pass."""
import program_trace


def read(run):
    return program_trace.span_per_pass(run, 'engine.tile_masks', scale=1e3)
