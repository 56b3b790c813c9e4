from repro.utils.counters import ComputeCounter

__all__ = ["ComputeCounter"]
