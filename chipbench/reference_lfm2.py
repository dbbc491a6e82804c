"""Plain float32 reference of the move-sequence policy built from an
``lfm2_moe`` spec (``rocalphago_tpu/models/seqpolicy.py``; LiquidAI
LFM2-8B-A1B, ``config.json``): forward pass, loss and gradients in
straightforward ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. The gated short
convolution is run ONE TOKEN AT A TIME, as a cache of the last
``conv_L_cache − 1`` gated inputs would run it (a ``lax.scan`` over
the row's tokens: no pad, no shifted slice); attention under a dense
``S × S`` mask per head; a Python loop over the held experts, each
run on every token and weighted by zero where it was not chosen; no
kernel, no ragged product, no sorting, no bf16.

It reads the program's parameter tree (the names are the interface)
and the program's spec kwargs: the published config's keys plus the
held share (``layers_held``, ``vocab_held``, ``experts_held``,
``expert_offset``). Nothing else is shared with the
program; the norm, the gated MLP, the mean cross-entropy
(``loss_of``), ``pick``/``put`` and the two error norms are
``reference_laguna.py``'s, the rotary ``reference_ling.py``'s, one
head's dense masked softmax and the held experts' weighted sum
``reference_xing.py``'s — references too.

The model, one row. ``d`` = ``hidden_size``; pre-norm blocks::

    x <- x + Mixer_i(RMSNorm(x));  x <- x + FFN_i(RMSNorm(x))
    Mixer_i = CONV if layer_types[i] == "conv" else ATTN
    logits = RMSNorm(x) Emb^T                       (the tied head)

    CONV(x): [B | C | x~] = x W_in  (d each, in that order)
        u = B * x~
        v_t = sum_{j < K} w_j * u_{t - (K-1) + j}   (K = conv_L_cache,
              depthwise, no bias, u = 0 before the row's start)
        y = (C * v) W_out
    ATTN(x): H heads of hd = d / H, G key/value heads
        q = RMSNorm_hd(x W_q), k = RMSNorm_hd(x W_k) per head (one
        scale of hd each); v = x W_v
        plain rotary (rope_theta) on the whole head, half-split
        a_h = softmax(q_h k_g(h)^T / sqrt(hd) + causal) v_g(h)
        y = concat_h(a_h) W_o                       (no gate, no bias)
    dense FFN (the held leading dense layers): SwiGLU(intermediate_size)
    expert layer: s = sigmoid(x W_r) over all num_experts
        T = top-k(s + b);  w_e = s_e / sum_T s * routed_scaling_factor
        out = sum_{e in T, held} w_e E_e(x)           (no shared expert)

``ASSUMED`` lists what the config has no key for; the configuration
file carries the same list.

``blocks=True`` is for the chip: rows and heads are taken one at a
time (``lax.map``) and layers recomputed in the backward pass
(``jax.checkpoint``). The arithmetic is the same; a test holds the two
to each other.

``dtype=jnp.bfloat16`` is NOT the reference: it is the reading "what
if the float32 parts (router, softmax, norms, loss, the convolution
and its gating) were computed in the compute type", which the
checks below must refuse (``chipbench/lowered_reading_lfm2.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference_laguna import (  # noqa: F401  (re-exported)
    _mlp,
    _rms,
    loss_of,
    pick,
    put,
    relative_error,
    update_error,
)
from chipbench.reference_ling import _rotary
from chipbench.reference_xing import _head, sparse_ffn

#: what the published config has no key for, as this file and the
#: program compute it
ASSUMED = {
    "tie": "the head is tied to the embedding (logits = RMSNorm(x) "
           "Emb^T): the config has no tie_word_embeddings key, the "
           "LFM2 family ties, and the catalog's 8.3B parameters count "
           "8.34B tied against 8.47B untied",
    "router.scoring": "sigmoid scores; the choice by score plus the "
                      "selection bias, the weights the chosen scores "
                      "(the family's code)",
    "router.topk_eps": "the chosen weights renormalised as s / sum s; "
                       "the family's transformers code adds 1e-6 under "
                       "the sum, about 5e-7 of a weight, left out",
    "router.bias": "moved after each step by the rule of "
                   "DeepSeek-V3's loss-free balancing, b += 0.001 "
                   "sign(mean load - load), 0.001 assumed, loads from "
                   "this chip's tokens; 128 such steps in the "
                   "benchmark's set-up (as the Xing and Ling cells); "
                   "no auxiliary balance loss",
    "rope.pairing": "half-split rotary pairing (dimension i with i + "
                    "hd/2) on the whole 64-wide head",
    "norm": "RMSNorm x rsqrt(mean(x^2) + norm_eps) * scale, the scale "
            "starting at ones, for the blocks' norms, the final norm "
            "and the per-head q and k norms",
    "init": "normal(0.02) matrices and convolution taps, normal(0.002) "
            "router bias (two steps of the +-0.001 update), ones norms",
    "tokens": "move ids: board points 0-360, pass 361, game separator "
              "362; the rest of the held vocabulary is unused by Go "
              "data and the traffic draws over all of it",
}

# ---------------------------------------------------------- tolerances
#
# Set from chip readings at the cell's sizes (one v5e, 2 x 8,192
# positions; PERF.md section 6 has every one): what the program
# gave over its seeds, and the control - this file with
# ``dtype=jnp.bfloat16`` (router, softmax, norms, loss, the convolution
# and its gating in the compute type: the nearest precision below the
# configuration's) put in the program's place and held to the float32
# reference by the driver's own ``verify``
# (``chipbench/lowered_reading_lfm2.py``), three seeds - which has to
# come out not ``correct``, and is refused at each by the gated
# output's check alone. bf16 products through nine layers on a bf16
# residual stream put 1.3-1.4 % on a logit row and 6-20 % on a leaf's
# gradient, and the control reads 1.1-1.8 times that: no limit on the
# logits, the loss or a leaf can admit the one and refuse the other.
#
# The first step's loss and the sampled logit rows have no limit: the
# driver reports them (the loss's relative error, program 7e-6 to
# 5e-5 | control 2e-5 to 5e-5; the median row's relative L2 error,
# 0.0134-0.0137 | 0.0158-0.0160; the near and far halves' and the
# worst row's).

#: the first sampled short-convolution layer's gated output ``C *
#: conv(B * x~)`` as the program leaves it in float32, against this
#: file's from the product ``[B | C | x~]`` as the program's gating
#: read it: relative L2 error over the whole ``[rows, S, d]``. The
#: program computes it in float32 and reads float32 rounding
#: (4.41e-8 to 4.45e-8); the control rounds to bf16 at each step
#: (4.38e-3 to 4.39e-3). At the geometric middle of the two
MIX_TOLERANCE = 1.4e-5
#: relative L2 error of (first-step update / -lr) against the
#: reference's gradient beyond what storing the new weight in float32
#: adds (``update_error``), by the kind of leaf. A leaf left unchanged
#: reads 1; each limit is the geometric middle of the program's largest
#: reading over its seeds (given; the first round's cut among them) and
#: 1: room for fresh seeds above and a gross fault below
GRAD_TOLERANCE = {
    "embed": 0.28,          # 0.081
    "in_proj": 0.29,        # 0.083
    "attn/conv": 0.29,      # 0.0865
    "out_proj": 0.29,       # 0.083
    "q_norm": 0.35,         # 0.120
    "k_norm": 0.35,         # 0.121
    "q_proj": 0.30,         # 0.0876
    "experts": 0.41,        # 0.1668
    "router": 0.45,         # 0.205
}


def grad_tolerance(leaf: str) -> float:
    """The limit for a sampled leaf, by its path."""
    for kind, limit in GRAD_TOLERANCE.items():
        if kind in leaf:
            return limit
    raise KeyError(f"no tolerance for leaf {leaf!r}")


# ------------------------------------------------------------ the parts

def is_conv(i: int, kw: dict) -> bool:
    """Whether layer ``i`` is a gated short convolution."""
    return kw["layer_types"][i] == "conv"


def is_sparse(i: int, kw: dict) -> bool:
    """Whether layer ``i`` has routed experts."""
    return i >= kw["num_dense_layers"]


def token_by_token_conv(u, taps):
    """``u [S, C]``, ``taps [K, C]``: ``v_t = sum_j taps[j]
    u_{t-(K-1)+j}``, one token at a time from a cache of the last
    ``K - 1`` inputs, zeros before the row's start."""
    k = taps.shape[0]

    def token(cache, u_t):
        window = jnp.concatenate([cache, u_t[None]])       # [K, C]
        return window[1:], (taps * window).sum(0)

    return jax.lax.scan(token, jnp.zeros((k - 1, u.shape[1]), u.dtype),
                        u)[1]


def gated_mix(bcx, taps, dtype=jnp.float32):
    """``[B | C | x~]`` ``[S, 3d]`` -> ``C * conv(B * x~)`` ``[S, d]``
    in ``dtype``, the convolution token by token."""
    bcx, d = bcx.astype(dtype), bcx.shape[-1] // 3
    b, c, xt = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    return c * token_by_token_conv(b * xt, taps.astype(dtype))


def short_conv(p: dict, x):
    """The gated short convolution: ``x [S, d]`` (normed) -> ``[S,
    d]``."""
    dtype = x.dtype
    mixed = gated_mix(x @ p["in_proj"].astype(dtype), p["conv"], dtype)
    return mixed @ p["out_proj"].astype(dtype)


def attention(p: dict, x, kw: dict, blocks: bool = False):
    """GQA with per-head QK-norm and no gate: ``x [S, d]`` (normed) ->
    ``[S, d]``."""
    s_len, dtype = x.shape[0], x.dtype
    eps, h = kw["norm_eps"], kw["num_attention_heads"]
    g = kw["num_key_value_heads"]
    hd = kw["hidden_size"] // h
    w = {n: p[n].astype(dtype) for n in
         ("q_proj", "k_proj", "v_proj", "o_proj")}
    q = _rms((x @ w["q_proj"]).reshape(s_len, h, hd),
             p["q_norm"]["scale"], eps, dtype)
    k = _rms((x @ w["k_proj"]).reshape(s_len, g, hd),
             p["k_norm"]["scale"], eps, dtype)
    q = _rotary(q, kw["rope_theta"])
    k = _rotary(k, kw["rope_theta"])
    v = (x @ w["v_proj"]).reshape(s_len, g, hd)
    per_group, scale = h // g, 1.0 / math.sqrt(hd)
    if blocks:
        a = jax.lax.map(
            jax.checkpoint(lambda t: _head(t[0], t[1], t[2], scale)),
            (q.transpose(1, 0, 2),
             jnp.repeat(k.transpose(1, 0, 2), per_group, axis=0),
             jnp.repeat(v.transpose(1, 0, 2), per_group, axis=0))
        ).transpose(1, 0, 2)
    else:
        a = jnp.stack([_head(q[:, i], k[:, i // per_group],
                             v[:, i // per_group], scale)
                       for i in range(h)], axis=1)
    return a.reshape(s_len, h * hd) @ w["o_proj"]


def routed_weights(p: dict, x, kw: dict):
    """``[S, num_experts]``: each token's weight on every expert —
    sigmoid scores, the choice by score plus bias, the chosen scores
    renormalised and scaled; zero elsewhere.
    The bias gets no gradient: it moves indices only."""
    s = jax.nn.sigmoid(x @ p["router"].astype(x.dtype))
    _, chosen = jax.lax.top_k(s + p["router_bias"].astype(x.dtype),
                              kw["num_experts_per_tok"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    if kw["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return (jnp.zeros_like(s).at[rows, chosen].set(top)
            * kw["routed_scaling_factor"])


def layer(lp: dict, x, i: int, kw: dict, blocks: bool):
    """One pre-norm decoder layer on ``x [S, d]``; also the experts
    the tokens chose ``[S, num_experts]`` (None of a dense layer)."""
    eps, dtype = kw["norm_eps"], x.dtype
    n = _rms(x, lp["input_norm"]["scale"], eps, dtype)
    x = x + (short_conv(lp["attn"], n) if is_conv(i, kw)
             else attention(lp["attn"], n, kw, blocks))
    n = _rms(x, lp["post_attn_norm"]["scale"], eps, dtype)
    if is_sparse(i, kw):
        weights = routed_weights(lp["ffn"], n, kw)
        return x + sparse_ffn(lp["ffn"], n, kw, shared=False,
                              weights=weights), weights > 0
    f = {k: v.astype(dtype) for k, v in lp["ffn"].items()}
    return x + _mlp(n, f["gate_proj"], f["up_proj"], f["down_proj"]), None


def _row(params: dict, ids, kw: dict, blocks: bool, dtype):
    """One row: ids ``[S]`` -> logits ``[S, vocab_held]`` and each
    expert layer's choices ``[layers, S, num_experts]`` (bool)."""
    p = params["params"]
    x = p["embed"][ids].astype(dtype)
    chosen = []
    for i in range(kw["layers_held"]):
        fn = lambda lp, x, i=i: layer(lp, x, i, kw, blocks)  # noqa: E731
        x, picked = (jax.checkpoint(fn) if blocks else fn)(
            p[f"layer{i}"], x)
        if picked is not None:
            chosen.append(picked)
    n = _rms(x, p["norm"]["scale"], kw["norm_eps"], dtype)
    if not chosen:
        chosen = [jnp.zeros((0, kw["num_experts"]), bool)]
    return n @ p["embed"].astype(dtype).T, jnp.stack(chosen)


def forward(params: dict, ids, kw: dict, blocks: bool = False,
            dtype=jnp.float32, choices: bool = False):
    """ids ``[B, S]`` -> logits ``[B, S, vocab_held]``; with
    ``choices`` also the experts each token chose, bool ``[expert
    layers, B, S, num_experts]``."""
    with jax.default_matmul_precision("highest"):
        if blocks:
            logits, chosen = jax.lax.map(
                lambda r: _row(params, r, kw, True, dtype), ids)
        else:
            logits, chosen = (jnp.stack(x) for x in zip(*(
                _row(params, r, kw, False, dtype) for r in ids)))
    if choices:
        return logits, chosen.swapaxes(0, 1)
    return logits


def loss(params: dict, ids, labels, kw: dict, blocks: bool = False,
         dtype=jnp.float32):
    return loss_of(forward(params, ids, kw, blocks, dtype), labels)


def loss_and_grads(params: dict, ids, labels, kw: dict, paths=None,
                   blocks: bool = False, dtype=jnp.float32):
    """The loss and its gradient: with respect to every leaf, or to
    the leaves ``paths`` names only."""
    if paths is None:
        return jax.value_and_grad(loss)(params, ids, labels, kw,
                                        blocks, dtype)
    return jax.value_and_grad(
        lambda leaves: loss(put(params, leaves), ids, labels, kw,
                            blocks, dtype))(pick(params, paths))
