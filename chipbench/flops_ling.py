"""Operations and bytes the train step of a ``bailing_hybrid``
configuration needs, from shapes and from the routed-pair count
(never from XLA's cost analysis, which counts what the compiler chose
to execute).

One multiply-add is two operations. A configuration is a file of
``configs/`` with the published keys: ``num_experts`` and
``vocab_size`` there are what is HELD, ``num_hidden_layers`` the
layers held (held layer ``i`` is the published layer ``i``),
``first_k_dense_replace`` the dense ones among them and
``num_nextn_predict_layers`` the multi-token-prediction modules held
(``published`` keeps the model's). Norms, rotary, sigmoids, SiLU, the
softmax, the L2 norms and the embedding lookups are left out.

The delta rule's recurrence is counted as the algorithm needs it,
token by token, whatever chunking implements it: per head and token
``d_k·d_v`` multiplies for the decay and ``2·d_k·d_v`` each for
``Sᵀk``, the rank-one update and ``Sᵀq`` — ``7·d_k·d_v``. The
chunkwise form runs more (the pairwise products and the inverse
inside a chunk); those are how, not what.
"""

from __future__ import annotations

from chipbench.flops_seq import causal_pairs

#: bytes of an element of the compute type, and of float32
BF16, F32 = 2, 4


def is_latent(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["layer_group_size"] == 0


def latent_layers(cfg: dict) -> int:
    """Held layers of latent attention, and the MTP module's block."""
    return sum(is_latent(cfg, i)
               for i in range(cfg["num_hidden_layers"])) \
        + cfg["num_nextn_predict_layers"]


def delta_layers(cfg: dict) -> int:
    """Held layers of Kimi delta attention."""
    return sum(not is_latent(cfg, i)
               for i in range(cfg["num_hidden_layers"]))


def expert_blocks(cfg: dict) -> int:
    """Held layers with routed experts, and the MTP module's block."""
    return (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
            + cfg["num_nextn_predict_layers"])


def delta_projection_mults(cfg: dict) -> int:
    """Multiply-adds per token of one delta layer outside its
    recurrence: ``q, k, v``, the decay's and the gate's projections
    and ``o`` (``d × H·head_dim`` each), ``beta`` (``d × H``) and
    the three convolutions' taps."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    wide = h * cfg["head_dim"]
    return (6 * d * wide + d * h
            + 3 * cfg["short_conv_kernel_size"] * wide)


def recurrence_flops_per_token(cfg: dict) -> int:
    """Operations per token of one delta layer's recurrence:
    ``7·d_k·d_v`` a head (module docstring)."""
    return 7 * cfg["num_attention_heads"] * cfg["head_dim"] ** 2


def latent_projection_mults(cfg: dict) -> int:
    """Multiply-adds per token of one latent layer's projections: no
    low-rank query path, a gate per head."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (d * h * qk
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + d * h + h * cfg["v_head_dim"] * d)


def attention_flops(cfg: dict, seq: int) -> int:
    """Forward operations of one latent layer's scores and weighted
    values over one row, on the unmasked pairs, at the published
    head sizes."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (2 * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"])
            * causal_pairs(seq))


def expert_flops_per_pair(cfg: dict) -> int:
    """Forward operations of one routed expert on one token."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_flops_per_token(cfg: dict) -> int:
    """Forward operations per token of everything every token takes
    but latent attention's scores, over the whole step."""
    d = cfg["hidden_size"]
    mults = delta_layers(cfg) * delta_projection_mults(cfg)
    mults += latent_layers(cfg) * latent_projection_mults(cfg)
    mults += cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"]
    mults += expert_blocks(cfg) * (
        d * cfg["published"]["num_experts"]
        + 3 * d * cfg["num_shared_experts"]
        * cfg["moe_shared_expert_intermediate_size"])
    mults += (1 + cfg["num_nextn_predict_layers"]) * d * cfg["vocab_size"]
    mults += cfg["num_nextn_predict_layers"] * 2 * d * d    # eh_proj
    return (2 * mults
            + delta_layers(cfg) * recurrence_flops_per_token(cfg))


def forward_flops(cfg: dict, rows: int, seq: int,
                  held_pairs: float) -> float:
    """Forward operations of one step: ``held_pairs`` is the
    token–expert pairs that landed on held experts in the step, over
    all expert-bearing blocks (the program's
    ``moe_tokens_held_total``)."""
    return (rows * seq * dense_flops_per_token(cfg)
            + rows * latent_layers(cfg) * attention_flops(cfg, seq)
            + held_pairs * expert_flops_per_pair(cfg))


def train_step_flops(cfg: dict, rows: int, seq: int,
                     held_pairs: float) -> float:
    """Forward + backward of one SGD step: 3 × forward. Recomputation
    does not count."""
    return 3 * forward_flops(cfg, rows, seq, held_pairs)


# ------------------------------------------------ the delta rule's scan

def scan_flops(cfg: dict, rows: int, seq: int) -> int:
    """Operations one train step needs of the recurrence, over the
    delta layers: 3 × forward."""
    return (3 * rows * seq * delta_layers(cfg)
            * recurrence_flops_per_token(cfg))


def scan_bytes(cfg: dict, rows: int, seq: int) -> int:
    """HBM bytes one train step needs of the recurrence, from ``q, k,
    v, g, beta`` to ``o``: the forward reads ``q, k, v`` and writes
    ``o`` in the compute type and reads the log-decay ``g`` in
    float32 and ``beta`` — each once; the backward reads them and
    ``o``'s cotangent and writes theirs — each once. The chunk
    states a backward pass keeps or recomputes are how, not what."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    forward = 4 * h * hd * BF16 + h * hd * F32 + h * F32
    return 3 * forward * rows * seq * delta_layers(cfg)
