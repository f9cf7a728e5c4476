"""Share of the traced window in which no operation ran on the device.

Source: the profiler trace of a steady stretch inside the window,
reduced by ``bench/trace.py``: ``100 * (1 - busy_s / window_s)``.
Nothing to read (no trace, or a trace with no device plane) gives None.
"""


def read(record: dict):
    """The idle share in percent, or None."""
    tr = record.get("trace")
    if not tr or tr["devices"] == 0 or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
