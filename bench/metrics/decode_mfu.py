"""Model step, decode: the operations of every decode tick in the window
(weights times live rows, attention over each row's live context, the
unembedding) over the wall time of the tick handlers, which each end in a
host sync, at the chip's peak, in %."""

from benchlib import flops


def read(run):
    ticks = run["rec"].ticks
    wall = sum(t["t1"] - t["t0"] for t in ticks)
    if not ticks or wall <= 0:
        return None
    work = sum(flops.tick_flops(run["model"], t["ctx"]) for t in ticks)
    return 100.0 * work / (wall * run["peak"]["flops"])
