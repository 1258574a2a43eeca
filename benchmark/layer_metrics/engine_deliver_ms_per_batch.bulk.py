"""Median of the engine's `serve:deliver` span over the window's batches (benchmark/deliver_records.py): the fetch thread's hand-out of a fetched batch, its counts into the registry and then its answers resolved, the part of the fetcher's serial time a batch that no other span covers. The reference has no such metric."""
from benchmark import deliver_records


def read(rec):
    return deliver_records.median_ms(rec)
