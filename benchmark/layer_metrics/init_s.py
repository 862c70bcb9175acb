"""Entry points: the host's clock around `hvd.init()`."""


def read(run):
    return run.init_s
