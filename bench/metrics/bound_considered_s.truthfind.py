"""The bound scan's ``considered`` mask (``bound.considered``: the incidence
shipped and the co-occurrence outside Ē), seconds per run."""
import program_trace


def read(run):
    return program_trace.span_per_pass(run, 'bound.considered')
