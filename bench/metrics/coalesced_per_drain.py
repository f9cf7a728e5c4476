"""Query requests answered per serving drain, over the window.

Source: ``QueryServer.stats()`` read at the window's close, after
``reset_stats()`` at its start: the sum of ``requests`` over the query
kinds divided by the sum of their ``batches`` (a drain that fuses a
union and an intersection segment counts once for each kind).
"""


def read(record: dict):
    """Requests per drain, or None without server statistics."""
    stats = record.get("server_stats")
    kinds = record.get("query_kinds") or []
    if not stats or not kinds:
        return None
    requests = sum(stats[k]["requests"] for k in kinds)
    batches = sum(stats[k]["batches"] for k in kinds)
    return requests / batches if batches else None
