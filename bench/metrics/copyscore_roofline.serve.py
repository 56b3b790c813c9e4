"""The copyscore kernel's share of its int8 roofline, from the trace's kernel time."""
import layers


def read(run):
    return layers.roofline(run)
