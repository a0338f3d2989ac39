"""Seconds from process start to the window's opening: imports, weights
drawn on the device, programs compiled or loaded, warm-up and the start of
the traffic (host clock)."""


def read(run):
    return run.setup_s
