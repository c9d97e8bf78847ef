"""The median host time from calling the entry until it returns (the work
enqueued, not done), in ms, from the harness's ``bench.call`` spans."""

import statistics


def read(trace, record):
    calls = trace.calls()
    if not calls:
        return None
    return statistics.median((s.end - s.start) / 1e6 for s in calls)
