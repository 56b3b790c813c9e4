"""Exact rescores (``engine.rescore``, in HYBRID and INCREMENTAL), seconds per run."""
import program_trace


def read(run):
    return program_trace.span_per_pass(run, 'engine.rescore')
