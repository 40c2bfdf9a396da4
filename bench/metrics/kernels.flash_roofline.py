"""kernels.flash_roofline: the flash-attention kernels' share of their roofline.

The work of the terms of ``bench/flops/<family>.py`` that the family's
``KERNEL_SCOPES`` maps to ``kernels.flash`` (for a dense decoder, the
causal attention core: QK^T and AV over the lower triangle, forward and
backward, no recomputation) in the traced steps, at the chip's peak bf16
rate, over the device time of the operations under the program's
``kernels.flash`` scope (the forward kernel, its run again under remat,
and both backward kernels), summed over devices. At head_dim 128 the
kernels are bound by compute, so the roofline is the peak rate. None where
no operation ran under that scope (a program without the kernels, or one
whose attention took another path), or where the family maps no term to it.
"""

import os

from harness import scopes, spec

SCOPE = "kernels.flash"
#: the checkout this reader lies in, whose bench/flops holds the families
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(rec):
    busy = sum(by_scope.get(SCOPE, 0.0) for by_scope in scopes.seconds())
    if busy <= 0 or rec.steps_traced == 0:
        return None
    c = rec.cell.config
    flops = spec.family_module("flops", c["family"], ROOT)
    per_token = sum(v for term, v in flops.terms(c, rec.seq).items()
                    if flops.KERNEL_SCOPES.get(term) == SCOPE)
    if per_token <= 0:
        return None
    return 100.0 * per_token * rec.tokens_traced / (
        rec.peaks["bf16_flops_per_s"] * busy)
