"""95th percentile of every gap between consecutive tokens at the client,
over all requests due in the window."""
from stats import nearest_rank, token_gaps


def read(run):
    gaps = token_gaps(run.records)
    return 1e3 * nearest_rank(gaps, 95) if gaps else None
