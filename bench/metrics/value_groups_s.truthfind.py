"""Value groups built and made resident (``truth.value_groups``), seconds per run."""
import program_trace


def read(run):
    return program_trace.span_per_pass(run, 'truth.value_groups')
