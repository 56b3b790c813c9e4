"""Entry chunks gathered on the device (``engine.device_gathered_chunks``), per pass."""
import program_trace


def read(run):
    return program_trace.count_per_pass(run, 'engine.device_gathered_chunks')
