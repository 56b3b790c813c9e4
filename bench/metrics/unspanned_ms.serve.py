"""Time inside ``service.batch`` that no span names, ms per pass."""
import program_trace


def read(run):
    return program_trace.unspanned_per_pass(run, 'service.batch', scale=1e3)
