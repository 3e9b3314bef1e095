"""Median time to first token: from each request's due time to its first
token reaching the client, over every request due in the window."""
from stats import nearest_rank, ttfts


def read(run):
    return 1e3 * nearest_rank(ttfts(run.records), 50)
