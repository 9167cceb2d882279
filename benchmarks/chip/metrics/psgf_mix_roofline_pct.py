"""psgf_mix_roofline_pct: the fused downlink mix's share of its roofline.

The least time is the bytes the mix needs each round (``flops.mix_bytes``:
every client row read and written, the gate at one bit, the global vector
once; bound by memory bandwidth) times the rounds of the traced window, at
the chip's HBM bandwidth; the share is that over the device time of the
mix kernel's events.
"""
import re

# the kernel's own events: the op named after it, a custom call (other ops
# name it among their operands)
KERNEL = re.compile(r"^%\S*psgf_mix\S* = .*custom-call\(")


def read(rc):
    t = rc.trace_reduce.kernel_seconds(rc.trace, KERNEL.pattern)
    c = rc.counts
    if t is None or not c.get("rounds"):
        return None
    need = rc.flops.mix_bytes(c["clients"], c["dim"]) * c["rounds"]
    return 100.0 * need / rc.peaks["hbm_bytes_per_s"] / t
