"""Process start to the first due request: weights from the seed on the
device, warm-up of every program the cell calls, clients connected."""


def read(run):
    return run.setup_s
