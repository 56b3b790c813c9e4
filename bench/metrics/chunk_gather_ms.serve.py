"""Engine chunk gather (``engine.chunk_gather``), ms per pass."""
import program_trace


def read(run):
    return program_trace.span_per_pass(run, 'engine.chunk_gather', scale=1e3)
