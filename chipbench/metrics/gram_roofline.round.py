"""The Gram kernel's share of its roofline, in %, over the traced rounds.

Kernel time is the summed device duration of the Pallas kernel's events,
found by ``KERNEL`` in the trace's op names. The least time is the larger
of the eq.-3 FLOPs over the bf16 peak and the eq.-3 bytes over the HBM
bandwidth (``work.py``), for as many rounds as the window traced.
"""
from chipbench import work

# On the chip an op event's name is its HLO instruction: the kernel is a
# ``tpu_custom_call`` named after the jitted ``gram_stats_fleet`` wrapper,
# "%gram_stats_fleet.1 = (f32[1,2,32,32]...) custom-call(...),
# custom_call_target="tpu_custom_call", ..." (a trace taken on a v5e)
KERNEL = r'^%?gram_stats_fleet(\.\d+)? = .*custom_call_target="tpu_custom_call"'


def read(rec):
    if rec.unit != "round" or rec.trace is None or not rec.peaks:
        return None
    kernel_s = rec.trace.kernel_s(KERNEL)
    if kernel_s <= 0:
        return None
    k = rec.work["kernel"]
    t_min, _ = work.roofline(k["flops"] * rec.steps, k["bytes"] * rec.steps,
                             rec.peaks)
    return 100.0 * t_min / kernel_s
