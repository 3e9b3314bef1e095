"""Seconds from process start to the opening of the window: imports, weights
drawn on the device, engine, and the warm-up that loads (or, in a cold
checkout, compiles) every program the window runs."""


def read(run):
    return run.setup_s
