"""Chunks the query engine decoded (``QueryStats.chunks_opened``) per
query, over the window."""


def read(rec):
    n = rec.counters.get("queries")
    return None if not n else rec.counters["chunks_opened"] / n
