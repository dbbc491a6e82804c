"""Operations and bytes the train step of an ``lfm2_moe`` configuration
needs, from shapes and from the routed-pair count (never from XLA's
cost analysis, which counts what the compiler chose to execute).

One multiply-add is two operations. A configuration is a file of
``configs/`` with the published keys: ``num_experts`` and
``vocab_size`` there are what is HELD, ``num_hidden_layers`` the
layers held, the first ones of the model (``published`` keeps the
model's), each of the kind ``layer_types`` gives its index. Norms, rotary, the router's
sigmoid, SiLU, the short convolution's elementwise gating, the
softmax and the embedding lookup are left out of the operations: the
gating is counted in bytes, where it binds. The head is the tied
embedding's transpose, one product like an untied one.
"""

from __future__ import annotations

from chipbench.flops_seq import causal_pairs

#: bytes of an element of the compute type
BF16 = 2


def _kinds(cfg: dict) -> list:
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def conv_layers(cfg: dict) -> int:
    """Held layers of the gated short convolution."""
    return _kinds(cfg).count("conv")


def attention_layers(cfg: dict) -> int:
    """Held layers of softmax attention."""
    return _kinds(cfg).count("full_attention")


def expert_layers(cfg: dict) -> int:
    """Held layers with routed experts: all but the leading dense."""
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def conv_mults(cfg: dict) -> int:
    """Multiply-adds per token of one short-convolution layer:
    ``in_proj`` (``d × 3d``), ``out_proj`` (``d × d``) and the
    depthwise taps."""
    d = cfg["hidden_size"]
    return 4 * d * d + cfg["conv_L_cache"] * d


def attention_projection_mults(cfg: dict) -> int:
    """Multiply-adds per token of one attention layer's ``q, k, v,
    o``."""
    return (cfg["hidden_size"] * head_dim(cfg)
            * 2 * (cfg["num_attention_heads"]
                   + cfg["num_key_value_heads"]))


def attention_flops(cfg: dict, seq: int) -> int:
    """Forward operations of one attention layer's scores and weighted
    values over one row, on the unmasked pairs, at the published head
    width (whatever padding a kernel runs)."""
    return (2 * cfg["num_attention_heads"] * 2 * head_dim(cfg)
            * causal_pairs(seq))


def expert_flops_per_pair(cfg: dict) -> int:
    """Forward operations of one routed expert on one token."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_flops_per_token(cfg: dict) -> int:
    """Forward operations per token of every matrix product that
    every token takes, over the whole step: the mixers, the dense
    MLP, the routers over all published experts, the head."""
    d = cfg["hidden_size"]
    mults = (conv_layers(cfg) * conv_mults(cfg)
             + attention_layers(cfg) * attention_projection_mults(cfg)
             + cfg["num_dense_layers"] * 3 * d * cfg["intermediate_size"]
             + expert_layers(cfg) * d * cfg["published"]["num_experts"]
             + d * cfg["vocab_size"])
    return 2 * mults


def forward_flops(cfg: dict, rows: int, seq: int,
                  held_pairs: float) -> float:
    """Forward operations of one step: ``held_pairs`` is the
    token–expert pairs that landed on held experts in the step, over
    all expert layers (the program's ``moe_tokens_held_total``)."""
    return (rows * seq * dense_flops_per_token(cfg)
            + rows * attention_layers(cfg) * attention_flops(cfg, seq)
            + held_pairs * expert_flops_per_pair(cfg))


def train_step_flops(cfg: dict, rows: int, seq: int,
                     held_pairs: float) -> float:
    """Forward + backward of one SGD step: 3 × forward. Recomputation
    does not count."""
    return 3 * forward_flops(cfg, rows, seq, held_pairs)


# ------------------------------------------------ the attention kernel

def attention_kernel_flops(cfg: dict, rows: int, seq: int) -> int:
    """Operations one train step needs of the attention kernel, over
    the attention layers: the forward's two products on the unmasked
    pairs and the backward's four — 3 × forward."""
    return 3 * rows * attention_layers(cfg) * attention_flops(cfg, seq)


def attention_kernel_bytes(cfg: dict, rows: int, seq: int) -> int:
    """HBM bytes one train step needs of the attention kernel: the
    forward reads ``q, k, v`` and writes ``o``; the backward reads
    ``q, k, v, o, do`` and writes ``dq, dk, dv`` — each once, in the
    compute type, at the published head width."""
    q_like = cfg["num_attention_heads"] * head_dim(cfg)
    kv_like = 2 * cfg["num_key_value_heads"] * head_dim(cfg)
    per_token = (2 * q_like + kv_like) + (4 * q_like + 2 * kv_like)
    return BF16 * rows * seq * attention_layers(cfg) * per_token


# ------------------------------------- the short convolution's gating

def shortconv_mix_bytes(cfg: dict, rows: int, seq: int) -> int:
    """HBM bytes one train step needs of the gated convolution between
    ``in_proj`` and ``out_proj``, in the compute type: per token and
    layer the forward reads ``B, C, x̃`` and writes the gated ``y`` —
    ``4d`` elements; the backward reads ``dy, B, C, x̃`` and writes
    ``dB, dC, dx̃`` — ``7d``. The taps are a few thousand numbers and
    not counted; recomputation is how it is done, not what is
    needed."""
    return (BF16 * rows * seq * conv_layers(cfg)
            * 11 * cfg["hidden_size"])


# -------------------------------------------------- the grouped products

def expert_product_flops(cfg: dict, held_pairs: float) -> float:
    """Operations one train step needs of the held experts' grouped
    products (``jax.lax.ragged_dot``): 3 × the forward's three products
    on the pairs that landed here, as ``flops_seq``'s."""
    return 3 * held_pairs * expert_flops_per_pair(cfg)


def expert_product_bytes(cfg: dict, held_pairs: float) -> float:
    """HBM bytes one train step needs of the grouped products, counted
    as ``flops_seq.expert_product_bytes`` counts them, over this
    configuration's expert layers: every held expert's three matrices
    read forward, read again backward and their gradients written;
    ``2·hidden + 3·width`` elements a pair forward and twice that
    backward, in the compute type."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = 3 * expert_layers(cfg) * cfg["num_experts"] * 3 * d * f
    return BF16 * (weights + 3 * held_pairs * (2 * d + 3 * f))
