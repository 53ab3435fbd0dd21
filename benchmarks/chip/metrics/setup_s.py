"""setup_s: seconds from process start to the first due message: imports,
device start, pipeline build, payload pool, compile or cache load, warm-up."""


def read(run):
    return run.setup_s
