"""Model FLOP utilisation of stage 3, in percent: the required FLOPs of
the window's iterations (``bench.lib.flops.ppo_iteration``: forward and
backward, no recomputation) over the window's seconds times the chip's
peak bf16 rate."""


def read(rec):
    peaks = rec.get("peaks")
    if peaks is None or not rec.get("iterations"):
        return None
    work = rec["flops"]["total"] * rec["iterations"]
    return 100.0 * work / (rec["window_s"] * peaks.bf16_flops)
