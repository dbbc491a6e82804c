"""Operations and bytes the train step of an ``xing4_0`` configuration
needs, from shapes and from the routed-pair count (never from XLA's
cost analysis, which counts what the compiler chose to execute).

One multiply-add is two operations. A configuration is a file of
``configs/`` with the published keys: ``n_routed_experts`` and
``vocab_size`` there are what is HELD, ``num_hidden_layers`` the
layers held and ``first_k_dense_replace`` the dense ones among them
(``published`` keeps the model's). The multi-token-prediction module
is one more block of the last layer's kind, ``eh_proj`` and a second
head product. Norms, rotary, sigmoids, softmaxes, Sinkhorn, the
streams' elementwise mixing and the embedding lookups are left out of
the operations: the mixing is counted in bytes, where it binds.
"""

from __future__ import annotations

from chipbench.flops_seq import causal_pairs

#: bytes of an element of the compute type
BF16 = 2


def blocks(cfg: dict) -> int:
    """Decoder blocks a step runs: the held layers and the MTP's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def expert_blocks(cfg: dict) -> int:
    """Those of them with routed experts (the MTP block is of the
    last layer's kind)."""
    return blocks(cfg) - cfg["first_k_dense_replace"]


def sublayers(cfg: dict) -> int:
    """Hyper-connected sublayers: attention and FFN of every block."""
    return 2 * blocks(cfg)


def head_dims(cfg: dict) -> tuple:
    """(query/key head, value head) as published: 192 and 128."""
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def attention_flops(cfg: dict, seq: int) -> int:
    """Forward operations of one block's scores and weighted values
    over one row, on the unmasked pairs, at the published head sizes
    (whatever padding a kernel runs)."""
    qk, v = head_dims(cfg)
    return (2 * cfg["num_attention_heads"] * (qk + v)
            * causal_pairs(seq))


def latent_projection_mults(cfg: dict) -> int:
    """Multiply-adds per token of one block's five projections."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk, v = head_dims(cfg)
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"] + v)
            + h * v * d)


def coefficient_mults(cfg: dict) -> int:
    """Multiply-adds per token of one sublayer's ``x' phi`` products:
    ``n·d × (2n + n²)``."""
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * (2 * n + n * n)


def expert_flops_per_pair(cfg: dict) -> int:
    """Forward operations of one routed expert on one token."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_flops_per_token(cfg: dict) -> int:
    """Forward operations per token of every matrix product that
    every token takes, over the whole step."""
    d = cfg["hidden_size"]
    mults = blocks(cfg) * latent_projection_mults(cfg)
    mults += sublayers(cfg) * coefficient_mults(cfg)
    mults += cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"]
    mults += expert_blocks(cfg) * (
        d * cfg["published"]["n_routed_experts"]
        + 3 * d * cfg["n_shared_experts"] * cfg["moe_intermediate_size"])
    heads = 1 + cfg["num_nextn_predict_layers"]
    mults += heads * d * cfg["vocab_size"]
    mults += cfg["num_nextn_predict_layers"] * 2 * d * d    # eh_proj
    return 2 * mults


def forward_flops(cfg: dict, rows: int, seq: int,
                  held_pairs: float) -> float:
    """Forward operations of one step: ``held_pairs`` is the
    token–expert pairs that landed on held experts in the step, over
    all expert-bearing blocks (the program's
    ``moe_tokens_held_total``)."""
    return (rows * seq * dense_flops_per_token(cfg)
            + rows * blocks(cfg) * attention_flops(cfg, seq)
            + held_pairs * expert_flops_per_pair(cfg))


def train_step_flops(cfg: dict, rows: int, seq: int,
                     held_pairs: float) -> float:
    """Forward + backward of one SGD step: 3 × forward. Recomputation
    does not count."""
    return 3 * forward_flops(cfg, rows, seq, held_pairs)


# ------------------------------------------------ the attention kernel

def attention_kernel_flops(cfg: dict, rows: int, seq: int) -> int:
    """Operations one train step needs of the attention kernel, over
    all blocks: the forward's two products on the unmasked pairs and
    the backward's four — 3 × forward."""
    return 3 * rows * blocks(cfg) * attention_flops(cfg, seq)


def attention_kernel_bytes(cfg: dict, rows: int, seq: int) -> int:
    """HBM bytes one train step needs of the attention kernel: the
    forward reads ``q, k, v`` and writes ``o``; the backward reads
    ``q, k, v, o, do`` and writes ``dq, dk, dv`` — each once, in the
    compute type; a key and a value per head."""
    qk, v = head_dims(cfg)
    per_head = (2 * qk + 2 * v) + (2 * qk + 3 * v) + (2 * qk + v)
    return (BF16 * rows * seq * blocks(cfg)
            * cfg["num_attention_heads"] * per_head)


# ------------------------------------------------- the streams' mixing

def stream_mix_bytes(cfg: dict, rows: int, seq: int) -> int:
    """HBM bytes one train step needs of the passes over the streams
    (``H_pre X``; ``H_res X + H_post^T y``; their backward), in the
    compute type. Per token and sublayer, in units of ``d`` elements:
    the forward reads ``X`` (``n``) for ``u`` (1 written) and, once
    the sublayer has run, again (``n``) with ``y`` (1) to write the
    new ``X`` (``n``): ``3n + 2``. The backward reads the new
    streams' cotangent (``n``), ``X`` (``n``) and ``y`` (1) for
    ``dy`` (1 written) and the coefficients' gradients; once the
    sublayer's backward has run it reads the cotangent (``n``), ``X``
    (``n``) and ``du`` (1) again and writes ``dX`` (``n``): ``5n +
    3``. The recomputed forward is how it is done, not what is
    needed. Plus the streams' sum at each of the two exits."""
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    per_token = sublayers(cfg) * (8 * n + 5) * d
    per_token += (1 + cfg["num_nextn_predict_layers"]) * 2 * (n + 1) * d
    return BF16 * rows * seq * per_token
