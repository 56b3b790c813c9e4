"""HYBRID scans (``engine.bound``: round 1 and the round-2 bootstrap), seconds per run."""
import program_trace


def read(run):
    return program_trace.span_per_pass(run, 'engine.bound')
