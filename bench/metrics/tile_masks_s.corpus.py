"""Tile and chunk masks (``engine.tile_masks``), seconds per pass."""
import program_trace


def read(run):
    return program_trace.span_per_pass(run, 'engine.tile_masks')
