"""Kernel flash_attention: each chunk's causal self-attention over its own
KV.  Roofline time from the operations and bytes of every call in the
window over the kernel's summed device time, in %."""

from benchlib import flops, layers


def read(run):
    return layers.kernel_roofline(
        run, "flash_attention",
        [flops.flash_attention_cost(run["model"], c["n"])
         for c in run["rec"].chunks])
