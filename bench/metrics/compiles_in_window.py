"""Programs compiled inside the timed window.

Source: ``repro.engine.plans.trace_counts()``, read at the window's start
and close; the sum over query kinds of the difference. Every shape the
window uses is compiled in set-up, so this should read 0.
"""


def read(record: dict):
    """The number of plan traces in the window, or None."""
    compiles = record.get("compiles")
    if compiles is None:
        return None
    return float(sum(compiles.values()))
