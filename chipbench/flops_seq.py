"""Operations the move-sequence policy's train step needs, from
shapes and from the routed-pair count (never from XLA's cost
analysis, which counts what the compiler chose to execute — masked
halves of score blocks, recomputed layers).

One multiply-add is two operations. A configuration is a file of
``configs/`` with the published keys: ``num_experts`` and
``vocab_size`` there are what is HELD, ``num_hidden_layers`` the
layers held (``published`` keeps the model's). Norms, rotary, gates'
sigmoids, softmaxes and the embedding lookup are left out: together
under 0.5 % of a step.
"""

from __future__ import annotations


def causal_pairs(seq: int, window: int = 0) -> int:
    """Query–key pairs of one head over one row: ``j ≤ i`` and, with
    a window, ``i − j < window``."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_flops(cfg: dict, layer: int, seq: int) -> int:
    """Forward operations of one layer's scores and weighted values
    over one row (``q kᵀ`` and ``p v`` on the unmasked pairs)."""
    heads = cfg["num_attention_heads_per_layer"][layer]
    window = (cfg["sliding_window"]
              if cfg["layer_types"][layer] == "sliding_attention"
              else 0)
    return 2 * 2 * heads * cfg["head_dim"] * causal_pairs(seq, window)


def dense_flops_per_token(cfg: dict, layer: int) -> int:
    """Forward operations per token of one layer's matrix products
    that every token takes: projections and gate, then the dense MLP
    or the router and the shared expert."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h = cfg["num_attention_heads_per_layer"][layer]
    g = cfg["num_key_value_heads"]
    mults = 2 * d * h * hd + 2 * d * g * hd + d * h
    if cfg["mlp_layer_types"][layer] == "sparse":
        mults += d * cfg["published"]["num_experts"]
        mults += 3 * d * cfg["shared_expert_intermediate_size"]
    else:
        mults += 3 * d * cfg["intermediate_size"]
    return 2 * mults


def expert_flops_per_pair(cfg: dict) -> int:
    """Forward operations of one routed expert on one token."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def forward_flops(cfg: dict, rows: int, seq: int,
                  held_pairs: float) -> float:
    """Forward operations of one step: ``held_pairs`` is the
    token–expert pairs that landed on held experts in the step, over
    all sparse layers (the program's ``moe_tokens_held_total``)."""
    tokens = rows * seq
    total = 0
    for layer in range(cfg["num_hidden_layers"]):
        total += tokens * dense_flops_per_token(cfg, layer)
        total += rows * attention_flops(cfg, layer, seq)
    total += tokens * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return total + held_pairs * expert_flops_per_pair(cfg)


def train_step_flops(cfg: dict, rows: int, seq: int,
                     held_pairs: float) -> float:
    """Forward + backward of one SGD step: 3 × forward (one product
    for the activations' gradient and one for the weights').
    Recomputation does not count."""
    return 3 * forward_flops(cfg, rows, seq, held_pairs)


# ------------------------------------------------ the attention kernel

def attention_kernel_flops(cfg: dict, rows: int, seq: int) -> int:
    """Operations one train step needs of the attention kernel, over
    all held layers: the forward's two products on the unmasked pairs
    and the backward's four (``dV``, ``dP``, ``dQ``, ``dK``) — 3 ×
    forward. The scores a flash backward computes again, and the
    forward a recomputed layer runs again, are how it is done, not
    what is needed."""
    return 3 * rows * sum(attention_flops(cfg, layer, seq)
                          for layer in range(cfg["num_hidden_layers"]))


def attention_kernel_bytes(cfg: dict, rows: int, seq: int) -> int:
    """HBM bytes one train step needs of the attention kernel: the
    forward reads ``q, k, v`` and writes ``o``; the backward reads
    ``q, k, v, o, do`` and writes ``dq, dk, dv`` — each once, in the
    compute type (2 bytes)."""
    hd, g = cfg["head_dim"], cfg["num_key_value_heads"]
    total = 0
    for layer in range(cfg["num_hidden_layers"]):
        h = cfg["num_attention_heads_per_layer"][layer]
        q_like, kv_like = h * hd, 2 * g * hd
        total += (2 * q_like + kv_like) + (4 * q_like + 2 * kv_like)
    return 2 * rows * seq * total


# -------------------------------------------------- the grouped products

def expert_product_flops(cfg: dict, held_pairs: float) -> float:
    """Operations one train step needs of the held experts' grouped
    products (``jax.lax.ragged_dot``): the three products of the
    forward on the pairs that landed here, and for each the input's
    and the weight's gradient — 3 × forward. The forward a recomputed
    layer runs again does not count."""
    return 3 * held_pairs * expert_flops_per_pair(cfg)


def expert_product_bytes(cfg: dict, held_pairs: float) -> float:
    """HBM bytes one train step needs of the grouped products, in the
    compute type (2 bytes): every held expert's three matrices read
    by the forward, read again for the inputs' gradients, and their
    gradients written, once each (a step routes to every held
    expert); per pair the forward reads ``x`` and the gated product
    and writes ``g``, ``u`` and ``y`` — ``2·hidden + 3·width``
    elements — and the backward is taken as twice that."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    sparse = cfg["mlp_layer_types"][:cfg["num_hidden_layers"]].count(
        "sparse")
    weights = 3 * sparse * cfg["num_experts"] * 3 * d * f
    return 2 * (weights + 3 * held_pairs * (2 * d + 3 * f))
