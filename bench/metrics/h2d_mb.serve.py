"""Bytes shipped host to device (``engine.h2d_bytes``), MB per pass."""
import program_trace


def read(run):
    return program_trace.count_per_pass(run, 'engine.h2d_bytes', scale=1e-6)
