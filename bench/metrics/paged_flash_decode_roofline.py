"""Kernel paged_flash_decode (the fused append-and-attend of a decode
tick): roofline time from the operations and the live tokens' bytes of
every call in the window over the kernel's summed device time, in %."""

from benchlib import flops, layers


def read(run):
    return layers.kernel_roofline(
        run, "paged_flash_decode",
        [flops.paged_flash_decode_cost(run["model"], t["ctx"])
         for t in run["rec"].ticks])
