"""setup_s: seconds from process start of the harness to the first
timed request: device start, generation, store build and warm-up."""


def read(run):
    return run["setup_s"]
