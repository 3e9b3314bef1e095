"""Median queue wait, from the program's scheduler events: for each request
submitted between the window's start and the end of the traced third whose
first ``admit`` came before that end, that admission less the request's
``submit`` stamp.  Also prints the traced third's self time by program
span, the host-bound share, the starved dispatch-ahead share, its compiles
and the median TTFT's parts (one stderr line, where the program records
spans)."""
import program_spans


def read(run):
    program_spans.report(run)
    return program_spans.p50_ms(run, "submit", "admit")
