"""The dense decoder-only family (MHA or GQA, gated FFN): the default of
a configuration file that names no ``family``. Thin: the reference is
``lib/reference.py`` and the counts are ``lib/shapes.py``, which stay
where they are with their tests."""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

from ..lib import reference
from ..lib.reference import make_weights  # noqa: F401  (the contract's name)
from ..lib.shapes import (  # noqa: F401  (the contract's names)
    decode_step_bytes,
    decode_token_flops,
    kv_bytes_per_token,
    prefill_flops,
    weight_bytes,
)

REQUIRED_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
                 "num_key_value_heads", "head_dim", "vocab_size", "rms_norm_eps", "rope_theta")


def program_config(c: Dict[str, Any]) -> Dict[str, Any]:
    """Keyword arguments of the program's ``ModelConfig`` for the
    configuration file's (Hugging Face named) sizes."""
    heads = int(c["num_attention_heads"])
    return dict(
        name=str(c["model"]),
        vocab_size=int(c["vocab_size"]),
        d_model=int(c["hidden_size"]),
        n_layers=int(c["num_hidden_layers"]),
        n_heads=heads,
        n_kv_heads=int(c.get("num_key_value_heads", heads)),
        d_head=int(c.get("head_dim", int(c["hidden_size"]) // heads)),
        d_ff=int(c["intermediate_size"]),
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        activation=str(c.get("hidden_act", "silu")),
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        qkv_bias=bool(c.get("attention_bias", False)),
        max_seq_len=int(c["max_position_embeddings"]),
    )


def served_logits(cfg: Dict[str, Any], weights, token_rows: Sequence[List[int]],
                  spans: Sequence[Tuple[int, int]]):
    """All rows in one call of the reference, padded to one width (a
    multiple of 128); of each row, the ``n`` positions from ``first``
    on that its span ``(first, n)`` asks for."""
    import jax.numpy as jnp
    import numpy as np

    width = -(-max(len(x) for x in token_rows) // 128) * 128
    toks = np.zeros((len(token_rows), width), dtype=np.int32)
    for i, x in enumerate(token_rows):
        toks[i, : len(x)] = x
    full = reference.logits(cfg, weights, jnp.asarray(toks))
    return [full[i, first : first + n] for i, (first, n) in enumerate(spans)]
