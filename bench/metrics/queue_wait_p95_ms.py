"""95th percentile of a query request's wait in the server's queue.

Source: ``QueryServer.stats()["queue_wait_ms"]["p95"]``, read at the
window's close after ``reset_stats()`` at its start: each query
request's wait from its submit to the start of the serving drain that
served it (ingest blocks excluded). A program without the counter
gives None.
"""


def read(record: dict):
    """The p95 queue wait in milliseconds, or None."""
    stats = record.get("server_stats") or {}
    return (stats.get("queue_wait_ms") or {}).get("p95")
