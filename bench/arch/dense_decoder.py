"""A dense decoder-only LM (Qwen2 / Qwen3) in the program's terms.

Maps the published ``config.json`` onto what the program runs:
``arch_config`` gives its ``ArchConfig`` (the registry entry with every size
taken from the file), ``planner_profile`` the planner's ``ModelProfile`` and
``tiny`` the same configuration at toy widths for the CPU rehearsal.

``MAPPED`` and ``NEUTRAL`` are plain data that ``bench/harness/spec.py``
reads without importing JAX, and by which it refuses a configuration that
asks for something this family does not run:

- ``MAPPED``: the keys this family reads, here and in ``bench/flops`` and
  ``bench/reference``; True where the file must hold the key, False where
  the published configs of the family may leave it out (the default is in
  the comment);
- ``NEUTRAL``: keys that nothing here runs, each with the one value under
  which leaving it out changes nothing (``ANY``: a key that never changes
  the computation).

Nothing in this module imports JAX or the program until a function runs.
"""

from __future__ import annotations

import dataclasses

ANY = "any"                        # spec.ANY

MAPPED = {
    "model_type": True,            # "qwen2" (QKV bias) or "qwen3" (qk-norm)
    "num_hidden_layers": True,
    "hidden_size": True,
    "num_attention_heads": True,
    "num_key_value_heads": True,
    "head_dim": False,             # hidden_size // num_attention_heads
    "intermediate_size": True,
    "vocab_size": True,
    "tie_word_embeddings": True,
    "attention_bias": False,       # model_type == "qwen2"
    "rope_theta": True,
    "rms_norm_eps": True,
    "initializer_range": True,     # the reference's weight draw
}

NEUTRAL = {
    "hidden_act": "silu",          # the program's MLP is SwiGLU
    "use_sliding_window": False,   # every layer attends to the whole prefix
    # read only under use_sliding_window, which must be false:
    "sliding_window": ANY,
    "max_window_layers": ANY,
    "rope_scaling": None,
    "attention_dropout": 0.0,
    "architectures": ANY,
    "bos_token_id": ANY,
    "eos_token_id": ANY,
    "max_position_embeddings": ANY,
    "use_cache": ANY,
}

#: the sizes of ``tiny``; the key-value heads follow the published ratio
TINY = {"num_hidden_layers": 4, "hidden_size": 64, "num_attention_heads": 4,
        "intermediate_size": 128, "head_dim": 16, "vocab_size": 256}


def arch_config(c: dict):
    """The program's ``ArchConfig`` for the published config ``c``."""
    from repro.configs import get_config

    if c["model_type"] not in ("qwen2", "qwen3"):
        raise ValueError(f"model_type {c['model_type']!r} is not mapped: "
                         "dense_decoder runs qwen2 and qwen3")
    qwen2 = c["model_type"] == "qwen2"
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    return dataclasses.replace(
        get_config(c["registry"]), num_layers=int(c["num_hidden_layers"]),
        d_model=d, n_heads=h, n_kv=int(c["num_key_value_heads"]),
        d_head=int(c.get("head_dim") or d // h),
        d_ff=int(c["intermediate_size"]), vocab=int(c["vocab_size"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        qkv_bias=bool(c.get("attention_bias", qwen2)), qk_norm=not qwen2,
        ffn_mult=3, rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), sliding_window=0)


def planner_profile(c: dict, seq_len: int):
    """The planner's profile at ``seq_len``: the embedding, then one entry per
    layer, then the final norm and head. ``planner.pred_step_ratio`` cuts it
    at ``1 + layers/stages`` and relies on this layout."""
    from repro.core.profiles import transformer_profile

    cfg = arch_config(c)
    return transformer_profile(
        c["registry"], cfg.num_layers, cfg.d_model, cfg.n_heads, cfg.n_kv,
        cfg.d_ff, cfg.vocab, seq_len, d_head=cfg.head_dim)


def tiny(c: dict) -> dict:
    """``c`` at toy widths and depth: multi-head attention stays multi-head,
    grouped-query attention keeps two key-value heads."""
    mha = c["num_key_value_heads"] == c["num_attention_heads"]
    return dict(c, **TINY, num_key_value_heads=(
        TINY["num_attention_heads"] if mha else 2))
