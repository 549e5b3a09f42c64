"""Seconds of tracing, lowering and compiling (or loading from the
persistent cache) that fell inside the window, per window round
(``jax.monitoring`` durations)."""


def read(run):
    if run.window_rounds <= 0:
        return None
    return run.compile_in_window["seconds"] / run.window_rounds
