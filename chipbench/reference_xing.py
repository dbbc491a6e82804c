"""Plain float32 reference of the move-sequence policy built from an
``xing4_0`` spec (``rocalphago_tpu/models/seqpolicy.py``;
XingChen-AGI Xing4.0-29B-A4B, ``config.json``): forward pass of both
heads, the loss (main + λ · multi-token prediction) and its gradients,
in straightforward ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: per token an ``n × d``
stream matrix and ``n × n`` mixing matrices, a dense ``S × S`` mask
per head, a Python loop over the held experts, each run on every token
and weighted by zero where it was not chosen; no kernel, no ragged
product, no sorting, no bf16, nothing folded or fused by hand.

It reads the program's parameter tree (the names are the interface)
and the program's spec kwargs: the published config's keys plus the
held share (``layers_held``, ``vocab_held``, ``experts_held``,
``expert_offset``). Nothing else is shared with the program; the
rotary tables' frequencies, the norm, the gated MLP, the mean
cross-entropy (``xent``), ``pick``/``put`` and the two error norms are
``reference_laguna.py``'s, which is a reference too.

The model, one row. ``d`` = ``hidden_size``, ``H`` heads, ``n`` =
``hc_mult`` streams; state ``X [S, n, d]``::

    X_0 = Emb(ids) copied to the n streams
    each layer: X <- HC_attn(X; MLA);  X <- HC_ffn(X; FFN)
    h = sum_i X_i;  logits = RMSNorm(h) W_head

    HC(X; F), per token, its own parameters per sublayer:
        x' = RMSNorm(vec(X)) over n*d (learned scale, rms_norm_eps)
        H_pre  = sigmoid(a_pre  (x' phi_pre)  + b_pre)          [n]
        H_post = 2 sigmoid(a_post (x' phi_post) + b_post)       [n]
        H_res  = SK(clip(a_res mat(x' phi_res) + b_res, lo, hi)) [n, n]
        SK(M): M = exp(M); hc_sinkhorn_iters times
               M <- M / (rowsum(M) + hc_eps); M <- M / (colsum(M) + hc_eps)
        u = H_pre X;  y = F(RMSNorm(u));  X <- H_res X + H_post^T y

    MLA(x): c_q = RMSNorm(x W_qa); q = c_q W_qb -> H x (nope | rope)
        [c_kv | k_pe] = x W_kva; [k_nope | v] = RMSNorm(c_kv) W_kvb
        rotary (YaRN) on q's rope part and on k_pe, shared by all heads
        a_h = softmax(q_h [k_nope,h | k_pe]^T (nope+rope)^-1/2 m^2 + causal) v_h
        m = 0.1 mscale_all_dim ln(factor) + 1;  out = concat_h(a_h) W_o

    expert layer: s = sigmoid(x W_r) over all n_routed_experts
        T = top-k(s + b);  w_e = s_e / sum_T s * routed_scaling_factor
        out = sum_{e in T, held} w_e E_e(x) + E_shared(x)
    dense layer (the first first_k_dense_replace): SwiGLU(intermediate_size)

    MTP: h' = [RMSNorm(h) | RMSNorm(Emb(next ids))] W_eh copied to n
        streams; one more layer of the last layer's kind; sum; its own
        final RMSNorm; the main W_head
    loss = xent(logits, next ids)
         + λ xent(mtp logits[:-1], next ids[1:])   (the last position has
           no target)

``ASSUMED`` lists what the config has no key for; the configuration
file carries the same list.

``blocks=True`` is for the chip: rows, heads and layers are taken one
at a time (``lax.map``) and recomputed in the backward pass
(``jax.checkpoint``). The arithmetic is the same; a test holds the two
to each other.

``dtype=jnp.bfloat16`` is NOT the reference: it is the reading "what
if the float32 parts (router, softmax, norms, loss, the
hyper-connections' coefficients and Sinkhorn) were computed in the
compute type", which the tolerances below must refuse
(``chipbench/lowered_reading_xing.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference_laguna import (  # noqa: F401  (re-exported)
    _inv_freq,
    _mlp,
    _rms,
    pick,
    put,
    relative_error,
    update_error,
)
from chipbench.reference_laguna import loss_of as xent

#: what the published config has no key for, as this file and the
#: program compute it
ASSUMED = {
    "mtp.loss_weight": "lambda = 0.3 (DeepSeek-V3 report section 4.2)",
    "hc.norm": "hc_eps guards Sinkhorn's denominators; the streams' "
               "norm is an RMSNorm over n*d with rms_norm_eps and a "
               "learned scale",
    "hc.entry_exit": "the streams enter as n copies of the embedding "
                     "and leave as their sum; the MTP block's enter "
                     "as copies of h' and leave as their sum",
    "rope.pairing": "interleaved-pair rotary computed in half-split "
                    "form (what the transformers code's permutation "
                    "of q and k_pe makes of it; a fixed permutation "
                    "of W_qb's and W_kva's columns)",
    "router.bias": "moved after each step by the published rule, b "
                   "+= 0.001 sign(mean load - load), 0.001 assumed, "
                   "loads from this chip's tokens; 128 such steps in "
                   "the benchmark's set-up; no auxiliary balance loss",
    "init": "normal(0.02) matrices, normal(0.002) router bias (two "
            "steps of the published +-0.001 update), ones norms, alpha "
            "0.01, b_post 0, b_pre = logit(1/n), b_res 0 on and -4 off "
            "the diagonal (H_res starts near the identity)",
}
#: lambda
MTP_WEIGHT = 0.3

# ---------------------------------------------------------- tolerances
#
# Each is set from two chip readings at the cell's sizes (one v5e,
# 1 x 8,192 positions; PERF.md section 6, PR 30, has both): what the
# program gave over twenty-two seeds, and the control - this file with
# ``dtype=jnp.bfloat16`` (router, softmax, norms, loss, the
# hyper-connections' coefficients and Sinkhorn in the compute type)
# put in the program's place and held to the float32 reference by the
# driver's own ``verify`` (``chipbench/lowered_reading_xing.py``),
# four seeds - which has to come out not ``correct``. The two lie
# nearer each other than Laguna's: a top-4 of 64 sigmoid scores flips
# less than a top-10 of 256 (the program differs from the reference
# in 0.9-1.7 % of a layer's choices, the control in 1.6-2.7 %), and
# bf16 products through six blocks are most of either's error.

#: |loss - reference| / reference, first step. The precision hardly
#: moves it (program 1.4e-6 to 1.0e-4, control 1.1e-5 to 9.4e-5: a
#: mean over 8,192 positions and 0.3 x as many), so it takes the
#: accepted train cells' limit, fifty times the largest reading
from chipbench.reference import LOSS_TOLERANCE  # noqa: E402,F401

#: the sampled positions' logit rows, relative L2 error: the median
#: row of each head (program 0.0124-0.0140 | control 0.0157-0.0167;
#: 0.0105-0.0115 | 0.0128-0.0139). Both limits sit at the geometric
#: middle and each refuses the control on every seed. The worst row
#: (0.12-0.26) is reported and not limited: the largest of 64 swings
#: with the sample
LOGITS_MEDIAN_TOLERANCE = {"main": 0.0148, "mtp": 0.0121}
#: relative L2 error of (first-step update / -lr) against the
#: reference's gradient beyond what storing the new weight in
#: float32 adds (``update_error``), by the kind of leaf - the
#: program's readings, then the control's. The first two sit at the
#: geometric middle and each refuses the control on every seed.
#: ``phi_res``'s update is a thousandth of a float32 step of its
#: weights at the assumed start (alpha 0.01, streams a percent
#: apart), so its reading is what ``update_error`` leaves of pure
#: storage rounding - steady for the program, 2.5 x that for the
#: control. The others cannot separate (the same bf16 products, the
#: same flipped choices a little more often): guards against a gross
#: fault with room over the program's largest
GRAD_TOLERANCE = {
    "phi_res": 0.085,       # 0.041-0.056 | 0.131-0.139
    "eh_proj": 0.043,       # 0.034-0.039 | 0.047-0.052
    "embed": 0.085,         # 0.041-0.064 | 0.062-0.066
    "attn": 0.10,           # q_b 0.055-0.073 | 0.076-0.087;
    #                         kv_b 0.027-0.033 | 0.038-0.039
    "experts": 0.22,        # 0.091-0.176 | 0.167-0.209
    "router": 0.28,         # 0.172-0.249 | 0.237-0.272
}


def grad_tolerance(leaf: str) -> float:
    """The limit for a sampled leaf, by its path."""
    for kind, limit in GRAD_TOLERANCE.items():
        if kind in leaf:
            return limit
    raise KeyError(f"no tolerance for leaf {leaf!r}")


# ------------------------------------------------------------ the parts

def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rotary(x, kw: dict):
    """``x [S, H, rope]``: rotate every head by position, YaRN's
    frequencies, the tables scaled by mscale / mscale_all_dim."""
    r = kw["rope_scaling"]
    inv = _inv_freq({"rope_theta": kw["rope_theta"],
                     "rope_type": r["type"], "factor": r["factor"],
                     "original_max_position_embeddings":
                         r["original_max_position_embeddings"],
                     "beta_fast": r["beta_fast"],
                     "beta_slow": r["beta_slow"]}, x.shape[-1])
    ratio = (_mscale(r["factor"], r["mscale"])
             / _mscale(r["factor"], r["mscale_all_dim"]))
    angle = np.arange(x.shape[0])[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angle) * ratio, x.dtype)[:, None, :]
    sin = jnp.asarray(np.sin(angle) * ratio, x.dtype)[:, None, :]
    half = x.shape[-1] // 2
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def _head(q, k, v, scale: float):
    """One head: ``q, k [S, dqk]``, ``v [S, dv]`` under a dense
    causal ``S x S`` mask."""
    s_len = q.shape[0]
    i = jnp.arange(s_len)[:, None]
    j = jnp.arange(s_len)[None, :]
    scores = jnp.where(j <= i, (q @ k.T) * scale, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1) @ v


def latent_attention(p: dict, x, kw: dict, blocks: bool = False):
    """``x [S, hidden]`` (normed) -> ``[S, hidden]``."""
    s_len, dtype = x.shape[0], x.dtype
    eps, h = kw["rms_norm_eps"], kw["num_attention_heads"]
    nope, rope = kw["qk_nope_head_dim"], kw["qk_rope_head_dim"]
    rank, dv = kw["kv_lora_rank"], kw["v_head_dim"]
    w = {n: p[n].astype(dtype) for n in
         ("q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", "o_proj")}
    c_q = _rms(x @ w["q_a_proj"], p["q_a_norm"]["scale"], eps, dtype)
    q = (c_q @ w["q_b_proj"]).reshape(s_len, h, nope + rope)
    kv_a = x @ w["kv_a_proj"]
    c_kv = _rms(kv_a[:, :rank], p["kv_a_norm"]["scale"], eps, dtype)
    kv = (c_kv @ w["kv_b_proj"]).reshape(s_len, h, nope + dv)
    k_pe = _rotary(kv_a[:, None, rank:], kw)                # [S, 1, rope]
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], kw)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (s_len, h, rope))], -1)
    v = kv[..., nope:]
    r = kw["rope_scaling"]
    m = _mscale(r["factor"], r["mscale_all_dim"])
    scale = m * m / math.sqrt(nope + rope)
    if blocks:
        a = jax.lax.map(
            jax.checkpoint(lambda t: _head(t[0], t[1], t[2], scale)),
            (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
             v.transpose(1, 0, 2))).transpose(1, 0, 2)
    else:
        a = jnp.stack([_head(q[:, i], k[:, i], v[:, i], scale)
                       for i in range(h)], axis=1)
    return a.reshape(s_len, h * dv) @ w["o_proj"]


def routed_weights(p: dict, x, kw: dict):
    """``[S, n_routed_experts]``: each token's weight on every
    expert. Scores by a sigmoid; the choice by score plus bias; the
    weights the chosen scores, renormalised and scaled; zero
    elsewhere. The bias gets no gradient: it moves indices only."""
    s = jax.nn.sigmoid(x @ p["router"].astype(x.dtype))
    _, chosen = jax.lax.top_k(s + p["router_bias"].astype(x.dtype),
                              kw["num_experts_per_tok"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    if kw["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return (jnp.zeros_like(s).at[rows, chosen].set(top)
            * kw["routed_scaling_factor"])


def sparse_ffn(p: dict, x, kw: dict, shared: bool = True, weights=None):
    """``x [S, hidden]`` (normed) -> the held experts' weighted part
    of the routed result plus (``shared``) the shared expert."""
    if weights is None:
        weights = routed_weights(p, x, kw)
    w = {n: p[n].astype(x.dtype) for n in
         ("experts_gate", "experts_up", "experts_down")}
    out = jnp.zeros_like(x)
    for e in range(kw["experts_held"]):
        out = out + weights[:, kw["expert_offset"] + e, None] * _mlp(
            x, w["experts_gate"][e], w["experts_up"][e],
            w["experts_down"][e])
    if shared:
        s = {n: v.astype(x.dtype) for n, v in p["shared"].items()}
        out = out + _mlp(x, s["gate_proj"], s["up_proj"],
                         s["down_proj"])
    return out


def sinkhorn(m, iters: int, eps: float):
    """``m [..., n, n]``: ``exp``, then ``iters`` times rows (sums
    over the last axis), then columns (a ``scan`` of the two lines:
    unrolled, twelve sublayers' twenty iterations and their
    transposes are most of this file's program)."""
    def once(m, _):
        m = m / (m.sum(-1, keepdims=True) + eps)
        return m / (m.sum(-2, keepdims=True) + eps), None

    return jax.lax.scan(once, jnp.exp(m), None, length=iters)[0]


def hyper_coefficients(p: dict, x, kw: dict):
    """``x [S, n, d]`` -> ``H_pre [S, n]``, ``H_post [S, n]``,
    ``H_res [S, n, n]`` in ``x``'s type."""
    s_len, n, d = x.shape
    dtype = x.dtype
    c = {k: v.astype(dtype) for k, v in p.items()}
    flat = _rms(x.reshape(s_len, n * d), p["norm"], kw["rms_norm_eps"],
                dtype)
    pre = jax.nn.sigmoid(c["alpha_pre"] * (flat @ c["phi_pre"])
                         + c["b_pre"])
    post = 2.0 * jax.nn.sigmoid(c["alpha_post"] * (flat @ c["phi_post"])
                                + c["b_post"])
    res = (c["alpha_res"] * (flat @ c["phi_res"]).reshape(s_len, n, n)
           + c["b_res"])
    res = sinkhorn(jnp.clip(res, kw["mhc_h_res_clamp_min"],
                            kw["mhc_h_res_clamp_max"]),
                   kw["hc_sinkhorn_iters"], kw["hc_eps"])
    return pre, post, res


def hyper_sublayer(p: dict, x, f, kw: dict):
    """``X <- H_res X + H_post^T F(H_pre X)`` on ``x [S, n, d]``."""
    pre, post, res = hyper_coefficients(p, x, kw)
    y = f(jnp.einsum("sn,snd->sd", pre, x))
    return (jnp.einsum("sij,sjd->sid", res, x)
            + post[:, :, None] * y[:, None, :])


def layer(lp: dict, x, sparse: bool, kw: dict, blocks: bool):
    """One decoder layer on ``x [S, n, d]``; also the experts the
    tokens chose ``[S, n_routed_experts]`` (None of a dense layer)."""
    eps, dtype = kw["rms_norm_eps"], x.dtype
    x = hyper_sublayer(
        lp["attn_hc"], x,
        lambda u: latent_attention(
            lp["attn"], _rms(u, lp["input_norm"]["scale"], eps, dtype),
            kw, blocks), kw)
    picked = []

    def ffn(u):
        n = _rms(u, lp["post_attn_norm"]["scale"], eps, dtype)
        if sparse:
            weights = routed_weights(lp["ffn"], n, kw)
            picked.append(weights > 0)
            return sparse_ffn(lp["ffn"], n, kw, weights=weights)
        f = {k: v.astype(dtype) for k, v in lp["ffn"].items()}
        return _mlp(n, f["gate_proj"], f["up_proj"], f["down_proj"])

    x = hyper_sublayer(lp["ffn_hc"], x, ffn, kw)
    return x, (picked[0] if picked else None)


def _row(params: dict, ids, next_ids, kw: dict, blocks: bool, dtype):
    """One row: ids, next ids ``[S]`` -> main logits, MTP logits
    ``[S, vocab_held]`` and each expert layer's choices (the MTP
    block's last) ``[layers, S, n_routed_experts]`` bool."""
    p, n = params["params"], kw["hc_mult"]
    eps = kw["rms_norm_eps"]
    chosen = []

    def run(lp, x, sparse):
        fn = lambda lp, x: layer(lp, x, sparse, kw, blocks)  # noqa: E731
        x, picked = (jax.checkpoint(fn) if blocks else fn)(lp, x)
        if picked is not None:
            chosen.append(picked)
        return x

    def copies(x):
        return jnp.repeat(x[:, None, :], n, axis=1)

    head = p["head"].astype(dtype)
    x = copies(p["embed"][ids].astype(dtype))
    last = kw["layers_held"] - 1
    for i in range(kw["layers_held"]):
        x = run(p[f"layer{i}"], x, i >= kw["first_k_dense_replace"])
    h = x.sum(axis=1)
    logits = _rms(h, p["norm"]["scale"], eps, dtype) @ head
    joined = jnp.concatenate([
        _rms(h, p["mtp_hnorm"]["scale"], eps, dtype),
        _rms(p["embed"][next_ids], p["mtp_enorm"]["scale"], eps, dtype),
    ], axis=-1)
    x = copies(joined @ p["mtp_eh_proj"].astype(dtype))
    x = run(p["mtp_layer"], x, last >= kw["first_k_dense_replace"])
    ahead = _rms(x.sum(axis=1), p["mtp_norm"]["scale"], eps, dtype) @ head
    if not chosen:
        chosen = [jnp.zeros((0, kw["n_routed_experts"]), bool)]
    return logits, ahead, jnp.stack(chosen)


def forward(params: dict, ids, next_ids, kw: dict, blocks: bool = False,
            dtype=jnp.float32, choices: bool = False):
    """ids, next ids ``[B, S]`` -> (main logits, MTP logits), each
    ``[B, S, vocab_held]``; with ``choices`` also the experts each
    token chose, bool ``[expert layers + the MTP block's, B, S,
    n_routed_experts]``."""
    with jax.default_matmul_precision("highest"):
        if blocks:
            logits, ahead, chosen = jax.lax.map(
                lambda r: _row(params, r[0], r[1], kw, True, dtype),
                (ids, next_ids))
        else:
            logits, ahead, chosen = (jnp.stack(x) for x in zip(*(
                _row(params, a, b, kw, False, dtype)
                for a, b in zip(ids, next_ids))))
    if choices:
        return (logits, ahead), chosen.swapaxes(0, 1)
    return logits, ahead


def loss_of(logits, ahead, labels):
    """(main + lambda x MTP, MTP): position ``i`` of the MTP head has
    read ids up to ``i + 1`` and predicts id ``i + 2``, the label at
    ``i + 1``; the last position has no target."""
    mtp = xent(ahead[:, :-1], labels[:, 1:])
    return xent(logits, labels) + MTP_WEIGHT * mtp, mtp


def loss(params: dict, ids, labels, kw: dict, blocks: bool = False,
         dtype=jnp.float32):
    return loss_of(*forward(params, ids, labels, kw, blocks, dtype),
                   labels)[0]


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
