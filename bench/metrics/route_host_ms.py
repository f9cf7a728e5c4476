"""Host milliseconds to route one ingest chunk to its owner shards.

Source: ``QueryServer.stats()["spans"]["ds.engine.ingest.route"]`` over
the window (since ``reset_stats()``): ``total_ms / count``. The span
holds the sharded engine's routing of one ``INGEST_BLOCK`` chunk of an
ingest call: grouping both orientations of its edges by owner shard,
filling and padding the per-shard panels, and uploading them. A program
without the span (a local engine, or one that predates it) gives None.
"""


def read(record: dict):
    """Host ms per routed chunk, or None."""
    spans = (record.get("server_stats") or {}).get("spans") or {}
    span = spans.get("ds.engine.ingest.route")
    if not span or not span["count"]:
        return None
    return span["total_ms"] / span["count"]
