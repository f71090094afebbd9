"""Operations and minimum bytes of the served DeepSeek-V2 model with one
chip's share of the routed experts, from shapes.

The contract is ``chipbench/flops.py``'s: the model's own counts, padded
rows and positions left out; ``m`` is a configuration's ``model`` section
(``n_experts`` the router's width, ``n_experts_held`` the experts this
chip holds); ``r`` one ``scls.slice`` record.

Latent attention has two forms with different costs (``models/mla.py``):
expanded, which applies the up projections ``k_up``/``v_up`` to every key
and attends as ordinary heads, and absorbed, which folds ``k_up`` into the
query and applies ``v_up`` after the weighted sum.  Each phase is counted
in the form that needs fewer FLOPs: expanded for a prefill, absorbed for
a decode step.

The routed experts are counted at ``top_k * n_experts_held / n_experts``
experts per token: the share of a token's top-k that lands on this chip's
experts when routing spreads evenly (0.75 of an expert here).  The least
bytes of a decode step count every weight outside the routed experts once
(the router in float32), the embedding rows looked up and each live row's
latent records, and no routed expert: the experts a step reads depend on
where its rows were routed, which the program counts itself (the decode
span's ``expert_reads``, read by ``moe_decode_roofline``).
"""
from __future__ import annotations

from typing import Dict, Iterable, Mapping


def _held(m: Mapping) -> int:
    return m.get("n_experts_held") or m["n_experts"]


def param_counts(m: Mapping) -> Dict[str, int]:
    """Parameters by part: one layer's attention matmuls, the dense MLP,
    one MoE layer's router, shared experts and held routed experts, the
    norms of a layer, the embedding and the head."""
    d, H = m["d_model"], m["n_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    r = m["kv_lora_rank"]
    attn = d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d
    expert = 3 * d * m["d_ff_expert"]
    return dict(attn=attn, layer_norms=2 * d + r, dense_mlp=3 * d * m["d_ff"],
                router=d * m["n_experts"],
                shared=m["n_shared_experts"] * expert,
                expert=expert, routed=_held(m) * expert,
                embed=m["vocab_size"] * d,
                head=0 if m.get("tie_embeddings") else m["vocab_size"] * d,
                final_norm=d)


def _layers(m: Mapping):
    nf = m.get("first_dense_layers", 0)
    return nf, m["n_layers"] - nf


def total_params(m: Mapping) -> int:
    c = param_counts(m)
    nf, nm = _layers(m)
    per = c["attn"] + c["layer_norms"]
    return (nf * (per + c["dense_mlp"])
            + nm * (per + c["router"] + c["shared"] + c["routed"])
            + c["embed"] + c["head"] + c["final_norm"])


def latent_bytes_per_token(m: Mapping, bytes_per_el: int = 2) -> int:
    """c_kv and the rotary key of one token over all layers."""
    return (m["n_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            * bytes_per_el)


def _token_flops(m: Mapping) -> float:
    """Matmul FLOPs of one token through every layer outside attention's
    keys: the attention projections without the up projections, the
    dense MLP, and per MoE layer the router, the shared experts and the
    routed experts at this chip's expected share."""
    c = param_counts(m)
    d, H = m["d_model"], m["n_heads"]
    proj = (d * H * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"])
            + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + H * m["v_head_dim"] * d)
    nf, nm = _layers(m)
    routed = m["top_k"] * _held(m) / m["n_experts"] * c["expert"]
    return 2 * (m["n_layers"] * proj + nf * c["dense_mlp"]
                + nm * (c["router"] + c["shared"] + routed))


def _up_flops(m: Mapping) -> int:
    """``k_up`` and ``v_up`` applied to one latent, all layers."""
    H, r = m["n_heads"], m["kv_lora_rank"]
    return (2 * m["n_layers"] * r * H
            * (m["qk_nope_head_dim"] + m["v_head_dim"]))


def _pair_flops(m: Mapping, absorbed: bool) -> int:
    """Scores and weighted sum of one (query, key) pair, all layers."""
    H, r, dr = m["n_heads"], m["kv_lora_rank"], m["qk_rope_head_dim"]
    if absorbed:
        per = 2 * H * (r + dr) + 2 * H * r
    else:
        per = 2 * H * (m["qk_nope_head_dim"] + dr + m["v_head_dim"])
    return m["n_layers"] * per


def _head_flops(m: Mapping) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def prefill_flops(m: Mapping, lengths: Iterable[int]) -> float:
    """Prefill of rows with ``lengths`` tokens, the fewer of its two
    forms (expanded: each key's up projections once, cheap pairs;
    absorbed: each query's, dearer pairs)."""
    total = 0.0
    for n in lengths:
        if n <= 0:
            continue
        pairs = n * (n + 1) // 2
        attn = n * _up_flops(m) + min(pairs * _pair_flops(m, False),
                                      pairs * _pair_flops(m, True))
        total += n * _token_flops(m) + attn + _head_flops(m)
    return total


def decode_step_flops(m: Mapping, contexts: Iterable[int]) -> float:
    """One decode step of rows whose new token sees ``contexts`` keys
    (itself included), the fewer of its two forms (expanded: every
    cached latent up-projected; absorbed: the query's only)."""
    total = 0.0
    for ctx in contexts:
        expanded = ctx * (_up_flops(m) + _pair_flops(m, False))
        absorbed = _up_flops(m) + ctx * _pair_flops(m, True)
        total += _token_flops(m) + min(expanded, absorbed) + _head_flops(m)
    return total


def decode_step_min_bytes(m: Mapping, contexts: Iterable[int],
                          bytes_per_el: int = 2) -> int:
    """The least HBM traffic of one decode step, routed experts left out:
    every other weight once (the router in float32; of the embedding
    table only the rows looked up) and each row's latent records."""
    contexts = list(contexts)
    c = param_counts(m)
    _, nm = _layers(m)
    other = total_params(m) - c["embed"] - nm * (c["routed"] + c["router"])
    weights = other * bytes_per_el + nm * c["router"] * 4
    lookups = len(contexts) * m["d_model"] * bytes_per_el
    return (weights + lookups
            + sum(contexts) * latent_bytes_per_token(m, bytes_per_el))


def expert_bytes(m: Mapping, bytes_per_el: int = 2) -> int:
    """One routed expert's weights (gate, up, down)."""
    return param_counts(m)["expert"] * bytes_per_el


def slice_flops(m: Mapping, r: Mapping) -> float:
    """Useful model FLOPs of one slice: the first prefill of each new
    row, the tokens re-prefilled after an eviction (their matmuls only, a
    lower bound), and one decode step per valid output token at its
    context."""
    total = prefill_flops(m, [c for c, f in zip(r["ctx"], r["fresh"]) if f])
    total += r["reprefill"] * _token_flops(m)
    for c, v in zip(r["ctx"], r["valid"]):
        total += decode_step_flops(m, range(c + 1, c + v + 1))
    return total


def decode_slice_min_bytes(m: Mapping, r: Mapping) -> int:
    """The least HBM bytes of one slice's decode program, routed experts
    left out: per step, the rows still producing valid tokens at their
    context then (``decode_step_min_bytes``)."""
    need = 0
    for k in range(max(r["valid"], default=0)):
        ctx = [c + k + 1 for c, v in zip(r["ctx"], r["valid"]) if v > k]
        need += decode_step_min_bytes(m, ctx)
    return need
