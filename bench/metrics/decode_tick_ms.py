"""Decode tick: mean wall time of the decode-tick handler
(``_on_decode_tick`` -> ``forward(mode="decode")`` + argmax, which ends in
a host sync), in milliseconds, over the window's ticks."""


def read(run):
    ticks = run["rec"].ticks
    if not ticks:
        return None
    return 1e3 * sum(t["t1"] - t["t0"] for t in ticks) / len(ticks)
