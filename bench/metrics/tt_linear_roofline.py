"""Share of its roofline the ``tt_linear`` kernel reached in the traced
window: the least time the chip needs for its calls (the larger of their
operations over peak FLOP/s and their bytes over HBM bandwidth, from the
configuration's ops module, ``KERNELS``) over the calls' device time."""
import devtrace


def read(run):
    return devtrace.roofline(run, "tt_linear")
