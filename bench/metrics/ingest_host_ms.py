"""Host milliseconds per ingested block before the device takes it.

Source: ``QueryServer.stats()["spans"]["ds.engine.ingest"]`` over the
window (since ``reset_stats()``): ``total_ms / count``. The span holds
``SketchEngine.ingest``'s host part: validation, the int32 cast, the
directed copy, padding, uploads and the asynchronous dispatch of the
accumulate. A program without the span gives None.
"""


def read(record: dict):
    """Host ms per ingest call, or None."""
    spans = (record.get("server_stats") or {}).get("spans") or {}
    span = spans.get("ds.engine.ingest")
    if not span or not span["count"]:
        return None
    return span["total_ms"] / span["count"]
