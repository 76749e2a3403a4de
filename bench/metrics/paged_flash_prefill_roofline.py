"""Kernel paged_flash_prefill: the queries of each chunk that follows
another over the paged history of the chunks before it.  Roofline time
from the operations and bytes of every such call in the window over the
kernel's summed device time, in %."""

from benchlib import flops, layers


def read(run):
    return layers.kernel_roofline(
        run, "paged_flash_prefill",
        [flops.paged_flash_prefill_cost(run["model"], c["n"], c["hist"])
         for c in run["rec"].chunks if c["hist"] > 0])
