"""Share of the sharded scatter's slots that are padding, in percent.

Source: ``QueryServer.stats()["events"]`` over the window (the event
counter deltas since ``reset_stats()``): each routed chunk adds its
directed edge slots to ``route_slots`` and the rest of its ``shards x
cap`` panel to ``route_padded``, where ``cap`` is the power-of-two
bucket of the fullest owner's part. The metric is
``100 * route_padded / (route_slots + route_padded)`` over the window's
chunks: the padding and the owners' imbalance together. A program
without these counters gives None.
"""


def read(record: dict):
    """Padded share of the routed slots in percent, or None."""
    events = (record.get("server_stats") or {}).get("events") or {}
    real, padded = events.get("route_slots"), events.get("route_padded")
    if real is None or padded is None or real + padded <= 0:
        return None
    return 100.0 * padded / (real + padded)
