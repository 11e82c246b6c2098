"""Seconds from process start to the window: imports, weights, engine
build, compiles and warm-up, and for an offline mix filling every slot."""


def read(run):
    return run.setup_s
