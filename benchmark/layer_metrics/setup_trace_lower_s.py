"""Seconds of set-up jax spent tracing to jaxprs and lowering to MLIR (the program's `compile` spans, stage trace|lower, before the window). The reference has no such metric."""
from benchmark.program_spans import compile_s_before_window


def read(rec):
    return compile_s_before_window(rec, ("trace", "lower"))
