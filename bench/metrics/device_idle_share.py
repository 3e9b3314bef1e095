"""Share of the traced window in which no operation ran on the device."""
import devtrace


def read(run):
    return devtrace.idle_share(run.trace)
