"""Process start to the first timed call: imports, device start, data,
compile or cache load, warm-up."""


def read(run):
    return run.setup_s
