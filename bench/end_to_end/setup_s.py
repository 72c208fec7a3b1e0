"""Set-up time: process start to window start (generate, ``put``,
compile-cache loads and warm-up)."""


def read(run):
    return run.setup_s
