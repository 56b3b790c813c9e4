"""Finalize outside the exact rescore (``engine.decide``), ms per pass."""
import program_trace


def read(run):
    return program_trace.span_per_pass(run, 'engine.decide', scale=1e3)
