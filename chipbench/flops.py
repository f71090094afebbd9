"""Operations and minimum bytes of the served dense decoder, from shapes.

The counts are the model's own, not the program's: padded rows and
padded positions do no useful work and are not counted, so a share of a
peak computed from them cannot pass 100% unless the time is too short.
``m`` is a configuration's ``model`` section (``chipbench/configs``).
"""
from __future__ import annotations

from typing import Dict, Iterable, Mapping


def param_counts(m: Mapping) -> Dict[str, int]:
    """Parameters by part: one layer's matmul weights, the rest of a layer
    (biases and norms), the input embedding and the output head."""
    d, D = m["d_model"], m["head_dim"]
    q, kv = m["n_heads"] * D, m["n_kv_heads"] * D
    matmul = d * q + 2 * d * kv + q * d + 3 * d * m["d_ff"]
    other = 2 * d + ((q + 2 * kv) if m.get("qkv_bias") else 0)
    embed = m["vocab_size"] * d
    head = 0 if m.get("tie_embeddings") else m["vocab_size"] * d
    return dict(layer_matmul=matmul, layer_other=other, embed=embed,
                head=head, final_norm=d)


def total_params(m: Mapping) -> int:
    c = param_counts(m)
    return (m["n_layers"] * (c["layer_matmul"] + c["layer_other"])
            + c["embed"] + c["head"] + c["final_norm"])


def kv_bytes_per_token(m: Mapping, bytes_per_el: int = 2) -> int:
    """K and V of one token over all layers."""
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * bytes_per_el


def _attn_flops(m: Mapping, ctx: int) -> int:
    """Scores and weighted sum of one query over ``ctx`` keys, all layers."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * ctx


def prefill_flops(m: Mapping, lengths: Iterable[int]) -> int:
    """Prefill of rows with ``lengths`` tokens: every token runs the
    layers and attends causally, and each row's last token runs the
    output head."""
    c = param_counts(m)
    per_tok = 2 * m["n_layers"] * c["layer_matmul"]
    head = 2 * m["d_model"] * m["vocab_size"]
    # the query at position i sees keys 1 .. i
    return sum(n * per_tok + head + _attn_flops(m, n * (n + 1) // 2)
               for n in lengths if n > 0)


def decode_step_flops(m: Mapping, contexts: Iterable[int]) -> int:
    """One decode step of rows whose new token sees ``contexts`` keys
    (itself included)."""
    c = param_counts(m)
    per_row = 2 * (m["n_layers"] * c["layer_matmul"]
                   + m["d_model"] * m["vocab_size"])
    return sum(per_row + _attn_flops(m, ctx) for ctx in contexts)


def decode_step_min_bytes(m: Mapping, contexts: Iterable[int],
                          bytes_per_el: int = 2) -> int:
    """The least HBM traffic of one decode step: every weight once (of the
    embedding table only the rows looked up), each row's resident K/V
    once, and the new token's K/V written."""
    contexts = list(contexts)
    B = len(contexts)
    weights = (total_params(m) - param_counts(m)["embed"]) * bytes_per_el
    lookups = B * m["d_model"] * bytes_per_el
    kv = sum(contexts) * kv_bytes_per_token(m, bytes_per_el)
    return weights + lookups + kv
