"""Pairs INCREMENTAL rescored (``incremental.candidates``), per run."""
import program_trace


def read(run):
    return program_trace.count_per_pass(run, 'incremental.candidates')
