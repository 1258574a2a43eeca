"""Seconds of set-up inside XLA's backend compile or, on a cache hit, the cache's retrieval (the program's `compile` spans, stage backend, before the window). The reference has no such metric."""
from benchmark.program_spans import compile_s_before_window


def read(rec):
    return compile_s_before_window(rec, ("backend",))
