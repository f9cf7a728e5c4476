"""Host milliseconds per rebuild of the propagation routing.

Source: ``repro.engine.plans.span_stats()["ds.engine.routing"]`` in the
harness's own process, read after the run: ``total_ms / count``. The
analytic cell (``bench/drivers/nbhd_jobs.py``) runs no server, so these
are the process's totals: they include the rebuild of the set-up job
(one of about 11 in a 30 s window) besides those of the window's jobs. A rebuild
consolidates the ingested edge chunks, pads the directed routing to its
shape bucket and uploads it. A program without the span gives None.
"""
import sys


def read(record: dict):
    """Host ms per routing rebuild, or None."""
    plans = sys.modules.get("repro.engine.plans")
    span_stats = getattr(plans, "span_stats", None)
    if span_stats is None:
        return None
    span = span_stats().get("ds.engine.routing")
    if not span or not span["count"]:
        return None
    return span["total_ms"] / span["count"]
