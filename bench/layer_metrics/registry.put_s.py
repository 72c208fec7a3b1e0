"""Seconds of ``MatrixRegistry.put`` (encode, verify, transfer), on the
harness's clock around the call."""


def read(run):
    return run.put_s
