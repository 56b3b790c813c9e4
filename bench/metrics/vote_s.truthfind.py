"""The rounds' votes (``truth.vote``), seconds per run."""
import program_trace


def read(run):
    return program_trace.span_per_pass(run, 'truth.vote')
