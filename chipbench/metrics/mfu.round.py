"""The whole round's share of the chip's bf16 peak, in %: the round's
eq.-3 FLOPs (statistics, fold, solve; ``work.py``) over the traced round
time times the peak of the trace's ``device_kind``."""


def read(rec):
    if rec.unit != "round" or rec.trace is None or not rec.peaks \
            or not rec.steps:
        return None
    round_s = rec.trace.window_s / rec.steps
    return 100.0 * rec.work["flops"] / (round_s
                                        * rec.peaks["bf16_flops_per_s"])
