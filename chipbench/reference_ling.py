"""Plain float32 reference of the move-sequence policy built from a
``bailing_hybrid`` spec (``rocalphago_tpu/models/seqpolicy.py``;
inclusionAI Ling-3.0-flash, ``config.json``): forward pass, loss and
gradients in straightforward ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. Kimi delta attention is
run as the recurrence it is, ONE TOKEN AT A TIME (a ``lax.scan`` over
the row's tokens: no chunk, no cumulative sum, no inverse, no
``e^−G``); latent attention under a dense ``S × S`` mask per head; a
Python loop over the held experts, each run on every token and
weighted by zero where it was not chosen; no kernel, no ragged
product, no sorting of pairs, no bf16.

It reads the program's parameter tree (the names are the interface)
and the program's spec kwargs: the published config's keys plus the
held share (``layers_held``, ``vocab_held``, ``experts_held``,
``expert_offset``). Nothing else is shared with the program; the
rotary frequencies, the norm, the gated MLP, the mean cross-entropy
(``xent``), ``pick``/``put`` and the two error norms are
``reference_laguna.py``'s and one head's dense masked softmax
``reference_xing.py``'s — references too.

The model, one row. ``d`` = ``hidden_size``, ``H`` heads of
``head_dim`` = ``d_k`` = ``d_v``; pre-norm blocks with a plain
residual::

    x <- x + Mixer_i(RMSNorm(x));  x <- x + FFN_i(RMSNorm(x))
    Mixer_i = MLA if (i + 1) % layer_group_size == 0 else KDA
    FFN_i = SwiGLU(intermediate_size) if i < first_k_dense_replace
            else the expert layer
    logits = RMSNorm(x) W_head

    KDA(x):  conv = depthwise causal convolution, short_conv_kernel_size
             taps a channel, no bias, zeros before the row's start
        q^ = SiLU(conv(x W_q)); k^ = SiLU(conv(x W_k)); v = SiLU(conv(x W_v))
        per head: q = q^ / |q^|_2 / sqrt(d_k);  k = k^ / |k^|_2
        beta = sigmoid(x W_beta)                              [H]
        g = kda_lower_bound sigmoid(exp(A_log_h) (x W_f + dt_bias))
                                                   in (-5, 0)^[H, d_k]
        per head, S_0 = 0 [d_k, d_v]:
            S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
                  + beta_t k_t v_t^T;    o_t = S_t^T q_t
        y = (RMSNorm_{d_v}(o) * sigmoid(x W_g)) W_o   (one scale of
            d_v shared by the heads; W_g full rank)

    MLA(x): q = x W_q -> H x (nope | rope)       (q_lora_rank null)
        [c_kv | k_pe] = x W_kva; [k_nope | v] = RMSNorm(c_kv) W_kvb
        plain rotary (rope_theta) on q's rope part and on k_pe,
        which every head shares
        a_h = softmax(q_h [k_nope,h | k_pe]^T (nope+rope)^-1/2 + causal) v_h
        y = concat_h(a_h sigmoid(x W_gate)_h) W_o        (head_wise)

    expert layer: s = sigmoid(x W_r) over all num_experts; s' = s + b
        n_group groups by index; a group scores the sum of its two
        largest s'; the topk_group best groups stay
        T = top-k of s' over what stays
        w_e = s_e / sum_T s * routed_scaling_factor
        out = sum_{e in T, held} w_e E_e(x) + E_shared(x)

    MTP (num_nextn_predict_layers 1; not in the benchmark's stage):
        h' = [RMSNorm(h) | RMSNorm(Emb(next ids))] W_eh; one MLA +
        expert layer; its own final RMSNorm; the main W_head
    loss = xent(logits, next ids)
         + mtp_loss_scaling_factor xent(mtp logits[:-1], next ids[1:])

``ASSUMED`` lists what the config has no key for; the configuration
file carries the same list.

``blocks=True`` is for the chip: rows, heads and layers are taken one
at a time (``lax.map``), the recurrence in segments of
``RECURRENCE_SEGMENT`` tokens, each recomputed in the backward pass
(``jax.checkpoint``) — token by token all the same. The arithmetic
is the same; a test holds the two to each other.

``dtype=jnp.bfloat16`` is NOT the reference: it is the reading "what
if the float32 parts (router, softmax, norms, loss, the log-decay,
its exponentials and the carried state) were computed in the compute
type", which the tolerances below must refuse
(``chipbench/lowered_reading_ling.py``).
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
from chipbench.reference_xing import _head  # one head, dense causal mask

#: what the published config has no key for, as this file and the
#: program compute it
ASSUMED = {
    "kda.gate": "g = kda_lower_bound * sigmoid(exp(A_log) * (x W_f + "
                "dt_bias)): the safe gate with its lower bound as "
                "fla/ops/kda has it (lower_bound, safe_gate); W_f "
                "full rank (no_kda_lora)",
    "kda.init": "A_log = log U(1, 16) per head, dt_bias the inverse "
                "softplus of a step log-uniform in [0.001, 0.1] "
                "(fla/layers/kda.py)",
    "kda.qk_norm": "use_qk_norm read as KDA's L2 norm of q and k per "
                   "head (eps 1e-6 under the root, as fla's l2norm) "
                   "and as latent attention's kv_a_norm; no further "
                   "per-head norm in the latent layers",
    "kda.out_norm": "group_norm_size 1 read as an RMSNorm per head "
                    "over d_v with one learned scale of d_v shared "
                    "by the heads",
    "kda.out_gate": "KDA's output gate is channel-wise and full rank "
                    "(no_kda_lora); head_wise gating belongs to the "
                    "latent layers",
    "kda.state": "the state starts at zero at a row's start and does "
                 "not reset at a game separator (no document mask)",
    "unread": "max_window_layers, partial_rotary_factor and "
              "rotary_dim (= qk_rope_head_dim) are read by nothing "
              "else",
    "rope.pairing": "interleaved-pair rotary (rope_interleave) "
                    "computed in half-split form: a fixed permutation "
                    "of W_q's and W_kva's columns",
    "aux_loss": "seq_aux true without a coefficient: no auxiliary "
                "balance loss",
    "router.bias": "moved after each step by the published rule, b "
                   "+= 0.001 sign(mean load - load), 0.001 assumed, "
                   "loads from this chip's tokens; 128 such steps in "
                   "the benchmark's set-up",
    "swiglu_limit": "expert_swiglu_limit_list and share_expert_"
                    "swiglu_limit_list are 0 on every held layer; the "
                    "config names the limit and not its form, so a "
                    "non-zero entry is refused",
    "init": "normal(0.02) matrices and convolution taps, normal("
            "0.002) router bias, ones norms",
    "tokens": "move ids: board points 0-360, pass 361, game "
              "separator 362; the rest of the held vocabulary is "
              "unused by Go data and the traffic draws over all of it",
}
#: tokens of the recurrence that are differentiated at a time under
#: ``blocks``: 64 segments' states kept, one segment's steps' states
#: alive in its backward pass
RECURRENCE_SEGMENT = 128

# ---------------------------------------------------------- tolerances
#
# Each is set from two chip readings at the cell's sizes (one v5e,
# 1 x 8,192 positions; PERF.md section 6, PR 32, has both): what the
# program gave over ten seeds, and the control - this file with
# ``dtype=jnp.bfloat16`` (router, softmax, norms, loss, the
# log-decay, its exponentials and the carried state in the compute
# type) put in the program's place and held to the float32 reference
# by the driver's own ``verify`` (``chipbench/lowered_reading_ling.
# py``), three seeds - which has to come out not ``correct``. bf16
# products through seven layers on a bf16 residual stream put 3.6-3.9
# % on a logit row (Laguna's five layers 2.0 %); a top-8 of 512 under
# a group limit flips more than the others' routers (the program
# differs from the reference in 2.6-6.3 % of a layer's choices,
# rising with depth; the control in 8.3-15.5 %), and with 128 pairs a
# held expert a flipped token is a large share of its gradient.

#: |loss - reference| / reference, first step. The precision hardly
#: moves it (program 5.2e-6 to 6.4e-5, control 9.3e-5 to 2.6e-4: a
#: mean over 8,192 positions), so it takes the accepted train cells'
#: limit, seventy-eight times the largest reading
from chipbench.reference import LOSS_TOLERANCE  # noqa: E402,F401

#: the sampled positions' logit rows, relative L2 error: the median
#: row (program 0.0356-0.0386 | control 0.1049-0.1080), the limit at
#: the geometric middle; it alone refuses the control on every seed.
#: The far half of the sample (past 4,096) reads 0.038-0.040 against
#: the near half's 0.032-0.037: no state is carried wrongly. The worst
#: row (0.045-0.108) is reported and not limited
LOGITS_MEDIAN_TOLERANCE = 0.064
#: relative L2 error of (first-step update / -lr) against the
#: reference's gradient beyond what storing the new weight in
#: float32 adds (``update_error``), by the kind of leaf - the
#: program's readings, then the control's. All but ``A_log`` sit at
#: the geometric middle of the nearest two and each refuses the
#: control on every seed. ``A_log`` cannot: its thirty-two numbers
#: move by a few float32 steps of themselves (rounding 0.02-0.05 of
#: the gradient), and most of its gradient comes through the few
#: channels whose gate is not saturated - a guard against a gross
#: fault, with room over the program's largest
GRAD_TOLERANCE = {
    "experts": 0.356,       # 0.147-0.311 | 0.409-0.498
    "router": 0.44,         # 0.225-0.366 | 0.534-0.625
    "embed": 0.157,         # 0.092-0.097 | 0.253-0.260
    "A_log": 0.30,          # 0.038-0.165 | 0.134-0.343
    "f_proj": 0.25,         # 0.143-0.155 | 0.405-0.429
    "b_proj": 0.157,        # 0.093-0.097 | 0.254-0.260
    "k_proj": 0.155,        # 0.092-0.095 | 0.253-0.256
    "k_conv": 0.154,        # 0.090-0.095 | 0.250-0.261
    "q_proj": 0.115,        # 0.066-0.086 | 0.155-0.162 (latent)
    "gate_proj": 0.107,     # 0.060-0.082 | 0.139-0.145 (latent)
}


def grad_tolerance(leaf: str) -> float:
    """The limit for a sampled leaf, by its path."""
    for kind, limit in GRAD_TOLERANCE.items():
        if kind in leaf:
            return limit
    raise KeyError(f"no tolerance for leaf {leaf!r}")


# ------------------------------------------------------------ the parts

def causal_conv(x, taps):
    """``x [S, C]``, ``taps [K, C]``: ``y_t = sum_j taps[j] x_{t-(K-1)+j}``
    with zeros before the row's start."""
    k, s_len = taps.shape[0], x.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(taps[j] * padded[j:j + s_len] for j in range(k))


def delta_recurrence(q, k, v, g, beta, blocks: bool = False):
    """The recurrence as written, token by token: ``q, k, g [S, H,
    d_k]``, ``v [S, H, d_v]``, ``beta [S, H]`` -> ``o [S, H, d_v]``."""
    s_len, h, dk = q.shape

    def token(state, x):
        q, k, v, g, beta = x
        state = jnp.exp(g)[..., None] * state
        seen = jnp.einsum("hkv,hk->hv", state, k)
        state = state + (beta[:, None] * k)[..., None] \
            * (v - seen)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q)

    def run(state, xs):
        return jax.lax.scan(token, state, xs)

    start = jnp.zeros((h, dk, v.shape[-1]), q.dtype)
    xs = (q, k, v, g, beta)
    segment = RECURRENCE_SEGMENT
    if not blocks or s_len <= segment or s_len % segment:
        return run(start, xs)[1]
    xs = jax.tree_util.tree_map(
        lambda a: a.reshape((s_len // segment, segment) + a.shape[1:]),
        xs)
    out = jax.lax.scan(jax.checkpoint(run), start, xs)[1]
    return out.reshape((s_len,) + out.shape[2:])


def delta_attention(p: dict, x, kw: dict, blocks: bool = False):
    """Kimi delta attention: ``x [S, hidden]`` (normed) -> ``[S,
    hidden]``."""
    s_len, dtype = x.shape[0], x.dtype
    h, hd = kw["num_attention_heads"], kw["head_dim"]
    w = {n: v.astype(dtype) for n, v in p.items() if n != "o_norm"}

    def mixed(name):
        y = causal_conv(x @ w[f"{name}_proj"], w[f"{name}_conv"])
        return jax.nn.silu(y).reshape(s_len, h, hd)

    def unit(y):
        return y / jnp.sqrt((y * y).sum(-1, keepdims=True) + 1e-6)

    q = unit(mixed("q")) / math.sqrt(hd)
    k = unit(mixed("k"))
    v = mixed("v")
    beta = jax.nn.sigmoid(x @ w["b_proj"])
    g = kw["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w["A_log"])[:, None]
        * (x @ w["f_proj"] + w["dt_bias"]).reshape(s_len, h, hd))
    o = delta_recurrence(q, k, v, g.astype(dtype), beta, blocks)
    o = _rms(o, p["o_norm"]["scale"], kw["rms_norm_eps"], dtype)
    gate = jax.nn.sigmoid(x @ w["g_proj"])
    return (o.reshape(s_len, h * hd) * gate) @ w["o_proj"]


def _rotary(x, theta: float):
    """``x [S, H, rope]``: plain rotary by position, half-split."""
    inv = _inv_freq({"rope_theta": theta}, x.shape[-1])
    angle = np.arange(x.shape[0])[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angle), x.dtype)[:, None, :]
    sin = jnp.asarray(np.sin(angle), x.dtype)[:, None, :]
    half = x.shape[-1] // 2
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def latent_attention(p: dict, x, kw: dict, blocks: bool = False):
    """``x [S, hidden]`` (normed) -> ``[S, hidden]``: no low-rank
    query path, a sigmoid gate per head."""
    s_len, dtype = x.shape[0], x.dtype
    eps, h = kw["rms_norm_eps"], kw["num_attention_heads"]
    nope, rope = kw["qk_nope_head_dim"], kw["qk_rope_head_dim"]
    rank, dv = kw["kv_lora_rank"], kw["v_head_dim"]
    w = {n: p[n].astype(dtype) for n in
         ("q_proj", "kv_a_proj", "kv_b_proj", "gate_proj", "o_proj")}
    q = (x @ w["q_proj"]).reshape(s_len, h, nope + rope)
    kv_a = x @ w["kv_a_proj"]
    c_kv = _rms(kv_a[:, :rank], p["kv_a_norm"]["scale"], eps, dtype)
    kv = (c_kv @ w["kv_b_proj"]).reshape(s_len, h, nope + dv)
    theta = kw["rope_theta"]
    k_pe = _rotary(kv_a[:, None, rank:], theta)             # [S, 1, rope]
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], theta)],
                        -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (s_len, h, rope))], -1)
    v = kv[..., nope:]
    scale = 1.0 / math.sqrt(nope + rope)
    if blocks:
        a = jax.lax.map(
            jax.checkpoint(lambda t: _head(t[0], t[1], t[2], scale)),
            (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
             v.transpose(1, 0, 2))).transpose(1, 0, 2)
    else:
        a = jnp.stack([_head(q[:, i], k[:, i], v[:, i], scale)
                       for i in range(h)], axis=1)
    a = a * jax.nn.sigmoid(x @ w["gate_proj"])[..., None]
    return a.reshape(s_len, h * dv) @ w["o_proj"]


def routed_weights(p: dict, x, kw: dict):
    """``[S, num_experts]``: each token's weight on every expert.
    Scores by a sigmoid; the choice by score plus bias, among the
    experts of the ``topk_group`` groups whose two best add up to
    most; the weights the chosen scores, renormalised and scaled;
    zero elsewhere. The bias gets no gradient: it moves indices
    only."""
    s = jax.nn.sigmoid(x @ p["router"].astype(x.dtype))
    biased = s + p["router_bias"].astype(x.dtype)
    groups, kept = kw["n_group"], kw["topk_group"]
    if groups > 1:
        by_group = biased.reshape(x.shape[0], groups, -1)
        two_best = jnp.sort(by_group, axis=-1)[..., -2:].sum(-1)
        # the kept-th largest group score is the threshold (ties at
        # it are as unlikely as ties among float32 sigmoids)
        least = jnp.sort(two_best, axis=-1)[:, -kept, None]
        biased = jnp.where((two_best >= least)[..., None], by_group,
                           -jnp.inf).reshape(biased.shape)
    _, chosen = jax.lax.top_k(biased, kw["num_experts_per_tok"])
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


def is_latent(i: int, kw: dict) -> bool:
    """Whether the layer of published index ``i`` is latent
    attention."""
    return (i + 1) % kw["layer_group_size"] == 0


def layer(lp: dict, x, latent: bool, sparse: bool, kw: dict,
          blocks: bool):
    """One decoder layer on ``x [S, d]``; also the experts the tokens
    chose ``[S, num_experts]`` (None of a dense layer)."""
    eps, dtype = kw["rms_norm_eps"], x.dtype
    n = _rms(x, lp["input_norm"]["scale"], eps, dtype)
    mixer = latent_attention if latent else delta_attention
    x = x + mixer(lp["attn"], n, kw, blocks)
    n = _rms(x, lp["post_attn_norm"]["scale"], eps, dtype)
    if sparse:
        weights = routed_weights(lp["ffn"], n, kw)
        return (x + sparse_ffn(lp["ffn"], n, kw, weights=weights),
                weights > 0)
    f = {k: v.astype(dtype) for k, v in lp["ffn"].items()}
    return x + _mlp(n, f["gate_proj"], f["up_proj"],
                    f["down_proj"]), None


def _row(params: dict, ids, next_ids, kw: dict, blocks: bool, dtype):
    """One row: ids (and, with an MTP module, next ids) ``[S]`` ->
    logits, MTP logits (None without the module) and each expert
    layer's choices (the MTP block's last) ``[layers, S,
    num_experts]`` bool."""
    p, eps = params["params"], kw["rms_norm_eps"]
    dense = kw["first_k_dense_replace"]
    chosen = []

    def run(lp, x, latent, sparse):
        fn = lambda lp, x: layer(  # noqa: E731
            lp, x, latent, sparse, kw, blocks)
        x, picked = (jax.checkpoint(fn) if blocks else fn)(lp, x)
        if picked is not None:
            chosen.append(picked)
        return x

    head = p["head"].astype(dtype)
    x = p["embed"][ids].astype(dtype)
    for i in range(kw["layers_held"]):
        x = run(p[f"layer{i}"], x, is_latent(i, kw), i >= dense)
    logits = _rms(x, p["norm"]["scale"], eps, dtype) @ head
    ahead = None
    if kw.get("num_nextn_predict_layers", 0):
        joined = jnp.concatenate([
            _rms(x, p["mtp_hnorm"]["scale"], eps, dtype),
            _rms(p["embed"][next_ids], p["mtp_enorm"]["scale"], eps,
                 dtype)], axis=-1)
        x = run(p["mtp_layer"], joined @ p["mtp_eh_proj"].astype(dtype),
                True, True)
        ahead = _rms(x, p["mtp_norm"]["scale"], eps, dtype) @ head
    if not chosen:
        chosen = [jnp.zeros((0, kw["num_experts"]), bool)]
    return logits, ahead, jnp.stack(chosen)


def forward(params: dict, ids, next_ids, kw: dict, blocks: bool = False,
            dtype=jnp.float32, choices: bool = False):
    """ids, next ids ``[B, S]`` -> (logits, MTP logits or None), each
    ``[B, S, vocab_held]``; with ``choices`` also the experts each
    token chose, bool ``[expert layers (+ the MTP block's), B, S,
    num_experts]``."""
    with jax.default_matmul_precision("highest"):
        if blocks:
            logits, ahead, chosen = jax.lax.map(
                lambda r: _row(params, r[0], r[1], kw, True, dtype),
                (ids, next_ids))
        else:
            rows = [_row(params, a, b, kw, False, dtype)
                    for a, b in zip(ids, next_ids)]
            logits, ahead, chosen = (
                None if part[0] is None else jnp.stack(part)
                for part in zip(*rows))
    if choices:
        return (logits, ahead), chosen.swapaxes(0, 1)
    return logits, ahead


def loss_of(logits, ahead, labels, kw: dict):
    """(main + ``mtp_loss_scaling_factor`` x MTP, MTP): position
    ``i`` of the MTP head has read ids up to ``i + 1`` and predicts
    id ``i + 2``, the label at ``i + 1``; the last position has no
    target. Without the module the second is 0."""
    main = xent(logits, labels)
    if ahead is None:
        return main, jnp.zeros_like(main)
    mtp = xent(ahead[:, :-1], labels[:, 1:])
    return main + kw["mtp_loss_scaling_factor"] * mtp, mtp


def loss(params: dict, ids, labels, kw: dict, blocks: bool = False,
         dtype=jnp.float32):
    return loss_of(*forward(params, ids, labels, kw, blocks, dtype),
                   labels, kw)[0]


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
