"""kernels.flash_roofline: the flash-attention kernels' share of their roofline.

The causal attention core's work the traced steps need (the attention term
of ``bench/flops``: QK^T and AV over the lower triangle, 2 x 2 x heads x
head_dim x S/2 per token and layer, three times over forward and backward;
no recomputation), at the chip's peak bf16 rate, over the device time of
the operations under the program's ``kernels.flash`` scope (the forward
kernel, its run again under remat, and both backward kernels), summed over
devices. At head_dim 128 the kernels are bound by compute, so the roofline
is the peak rate. None where no operation ran under that scope: a program
without the kernels, or one whose attention took another path.
"""

from harness import scopes

SCOPE = "kernels.flash"


def attention_flops_per_token(c: dict, seq_len: int) -> float:
    h = int(c["num_attention_heads"])
    hd = int(c.get("head_dim") or int(c["hidden_size"]) // h)
    return 3.0 * int(c["num_hidden_layers"]) * 2 * 2 * h * hd * (seq_len / 2)


def read(rec):
    busy = sum(by_scope.get(SCOPE, 0.0) for by_scope in scopes.seconds())
    if busy <= 0 or rec.steps_traced == 0:
        return None
    work = attention_flops_per_token(rec.cell.config, rec.seq) \
        * rec.tokens_traced
    return 100.0 * work / (rec.peaks["bf16_flops_per_s"] * busy)
