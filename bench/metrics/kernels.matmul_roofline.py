"""kernels.matmul_roofline: the matmul operations' share of their roofline.

The operations the traced steps need (``bench/flops/<family>.py``'s terms:
forward and backward, no recomputation) that run as matmul-class
operations, at the chip's peak bf16 rate, over the device time of every
matmul-class operation (convolution and convolution-fusion ops of the
trace), summed over devices. A term that the family's ``KERNEL_SCOPES``
maps to a device scope holding time in the window ran as that scope's
custom calls, whose time is not in the denominator, so its work is left
out too. Matmuls this large are bound by compute, so the roofline is the
peak rate. Time spent recomputing, or on the pipeline's fill and drain,
counts in the time and not in the work.
"""

import os

from harness import scopes, spec

#: the checkout this reader lies in, whose bench/flops holds the families
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(rec):
    busy = sum(d.matmul_s for d in rec.trace) if rec.trace else 0.0
    if busy <= 0 or rec.steps_traced == 0:
        return None
    c = rec.cell.config
    flops = spec.family_module("flops", c["family"], ROOT)
    ran = {s for by_scope in scopes.seconds()
           for s, t in by_scope.items() if t > 0}
    per_token = sum(v for term, v in flops.terms(c, rec.seq).items()
                    if flops.KERNEL_SCOPES.get(term) not in ran)
    return 100.0 * per_token * rec.tokens_traced / (
        rec.peaks["bf16_flops_per_s"] * busy)
