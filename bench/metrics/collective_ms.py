"""Device milliseconds of collective ops per served query segment.

Source: the traced stretch of the window, reduced by
``bench/collectives.py`` (by ``bench/drivers/serve_mix_sharded.py``,
while the profile exists): the union of each device's collective op
intervals (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all, synchronous or asynchronous), averaged over the devices,
over the number of ``ds.serve.segment`` spans that started in the
stretch. A run without that reduction, a trace without a device, or a
stretch without a served segment gives None.
"""


def read(record: dict):
    """Collective ms per served segment, or None."""
    coll = record.get("collectives")
    if not coll or not coll["devices"] or not coll["segments"]:
        return None
    return 1e3 * coll["collective_s"] / coll["segments"]
