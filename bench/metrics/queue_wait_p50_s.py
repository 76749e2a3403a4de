"""Event loop and CDSP planner: median wall time from a request's due
time to the start of its first chunk handler, over the window's requests."""

import statistics


def read(run):
    rec = run["rec"]
    waits = [rec.first_chunk[r] - rec.due[r] for r in rec.first_chunk
             if r in rec.due]
    return statistics.median(waits) if waits else None
