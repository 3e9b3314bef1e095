"""Median prefill wall time, from the program's scheduler events: for the
requests ``queue_wait_p50_ms`` reads, from their first ``admit`` to their
``first_token`` stamp (taken once the prefill logits are ready)."""
import program_spans


def read(run):
    return program_spans.p50_ms(run, "admit", "first")
