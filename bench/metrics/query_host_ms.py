"""The serving thread's host milliseconds per query segment.

Source: the ``spans`` of ``QueryServer.stats()`` over the window (span
totals since ``reset_stats()``): the time in ``ds.serve.segment`` (the
shared serving core) and ``ds.serve.account`` (access counters and
latency statistics) less the time in ``ds.engine.query.fetch`` (waiting
for the device and copying answers back), over the number of
``ds.serve.segment`` spans. A program without these spans gives None.
"""

SPANS = ("ds.serve.segment", "ds.serve.account", "ds.engine.query.fetch")


def read(record: dict):
    """Host ms per query segment outside the device wait, or None."""
    spans = (record.get("server_stats") or {}).get("spans") or {}
    if not all(name in spans for name in SPANS):
        return None
    seg, account, fetch = (spans[name] for name in SPANS)
    if not seg["count"]:
        return None
    return (seg["total_ms"] + account["total_ms"]
            - fetch["total_ms"]) / seg["count"]
