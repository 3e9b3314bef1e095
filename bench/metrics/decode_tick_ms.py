"""Mean device time of one execution of the decode program, from the
profiler trace."""
import devtrace


def read(run):
    return devtrace.program_ms(run.trace, "decode")
