"""Kernel launches (``jitcache.bucket_stats()["calls"]``, summed over
kernels, counted over the window) per MB of input."""


def read(rec):
    mb = rec.counters.get("input_mb")
    return None if not mb else sum(rec.calls_delta.values()) / mb
