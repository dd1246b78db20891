"""Share of its roofline, in percent, that the Pallas flash attention
kernels (forward and backward) reach over the traced stage-3
iterations: the least time the chip could take for each call's required
work (``bench.lib.flops.flash_fwd`` / ``flash_bwd`` from the call's
operand shapes, the two backward calls of a layer counted as one
backward) over the calls' device time in the trace."""
from bench.lib import flops as F
from bench.lib import kernels as K


def read(rec):
    t, peaks = rec.get("trace"), rec.get("peaks")
    if t is None or peaks is None:
        return None
    least = busy = 0.0
    for o in t.device_ops:
        ops = K.operands(o.detail)
        kind = K.classify(ops)
        if kind not in ("flash_fwd", "flash_bwd"):
            continue
        busy += o.end - o.start
        fn = F.flash_fwd if kind == "flash_fwd" else F.flash_bwd
        flops, bytes_ = fn(*K.flash_shape(ops))
        share = 1.0 if kind == "flash_fwd" else 0.5
        least += share * F.roofline_seconds(flops, bytes_, peaks.bf16_flops,
                                            peaks.hbm_bytes_per_s)
    return 100.0 * least / busy if busy > 0 else None
