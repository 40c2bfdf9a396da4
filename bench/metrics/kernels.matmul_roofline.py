"""kernels.matmul_roofline: the matmul operations' share of their roofline.

The matmul operations the traced steps need (``bench/flops``: forward and
backward, no recomputation) at the chip's peak bf16 rate, over the device
time of every matmul-class operation (convolution and convolution-fusion
ops of the trace), summed over devices. Matmuls this large are bound by
compute, so the roofline is the peak rate. Time spent recomputing, or on
the pipeline's fill and drain, counts in the time and not in the work.
"""


def read(rec):
    busy = sum(d.matmul_s for d in rec.trace) if rec.trace else 0.0
    if busy <= 0 or rec.steps_traced == 0:
        return None
    work = rec.flops_per_token * rec.tokens_traced
    return 100.0 * work / (rec.peaks["bf16_flops_per_s"] * busy)
