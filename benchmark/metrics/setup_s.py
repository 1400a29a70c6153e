"""End to end, every cell: seconds from process start to the first timed
tick, compile and warm-up included (host clock)."""


def read(run):
    return run.setup_s
