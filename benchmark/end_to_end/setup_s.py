"""Process start to the first timed step: imports, `hvd.init`, state made on
the device, the reference check, compile or cache load, warm-up."""


def read(run):
    return run.setup_s
