"""Seconds from the process's start to the window's opening: weights,
data, the index build with the cheap tower, the warm-up requests that
compile the programs the window uses, and the ramp."""


def read(ctx):
    return ctx["setup_s"]
