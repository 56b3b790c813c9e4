"""Time inside ``engine.detect`` that no span names, seconds per pass."""
import program_trace


def read(run):
    return program_trace.unspanned_per_pass(run, 'engine.detect')
