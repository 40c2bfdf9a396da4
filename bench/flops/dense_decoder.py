"""Operations a dense decoder-only LM needs per trained token.

Forward and backward of every matmul: 6 x (parameters in matmuls), plus
causal attention, whose query at position i meets i keys (on average S/2):
QK^T and AV are 2 x 2 x heads x head_dim x S/2 in the forward pass, three
times that over forward and backward. Recomputation does not count, nor do
elementwise operations, so every operation counted is a matmul's.
"""

from __future__ import annotations

#: The terms a Pallas kernel may run, keyed by the device scope of its
#: custom calls. A term mapped to a scope is run by custom calls under that
#: scope wherever that scope holds device time, so that time is never in
#: the matmul-class operations' (``convolution``, ``dot``, ``kOutput``
#: fusions), and a roofline over those operations leaves the term out.
KERNEL_SCOPES = {"attention": "kernels.flash"}


def terms(c: dict, seq_len: int) -> dict:
    """{term: operations per trained token}: ``matmul``, the blocks' and the
    head's weight matmuls; ``attention``, the causal attention core."""
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    kv, ff = int(c["num_key_value_heads"]), int(c["intermediate_size"])
    hd = int(c.get("head_dim") or d // h)
    layers, vocab = int(c["num_hidden_layers"]), int(c["vocab_size"])
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * ff
    matmul_params = layers * per_layer + d * vocab          # blocks + head
    attention = layers * 2 * 2 * h * hd * (seq_len / 2)
    return {"matmul": 6.0 * matmul_params, "attention": 3.0 * attention}


def flops_per_token(c: dict, seq_len: int) -> float:
    return sum(terms(c, seq_len).values())
