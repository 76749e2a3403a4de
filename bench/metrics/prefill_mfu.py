"""Model step, prefill: the layer stack's operations of every chunk the
window ran (projections, MLP and causal attention, useful work only) over
the device time of the prefill programs at the chip's peak, in %."""

from benchlib import flops, layers


def read(run):
    m = run["model"]
    work = sum(flops.linear_flops_per_token(m) * c["n"]
               + flops.attention_flops(m, flops.causal_pairs(c["n"],
                                                             c["hist"]))
               for c in run["rec"].chunks)
    return layers.step_mfu(run, "prefill", work)
