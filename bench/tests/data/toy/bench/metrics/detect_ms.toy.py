"""Engine detect (``engine.detect``), ms per pass."""
import program_trace


def read(run):
    return program_trace.span_per_pass(run, 'engine.detect', scale=1e3)
