"""Plain float32 reference of the move-sequence policy
(``rocalphago_tpu/models/seqpolicy.py``): Laguna-S-2.1's decoder
(poolside, ``config.json``, ``model_type: laguna``) — forward pass,
mean next-token cross-entropy and its gradients — in straightforward
``jax.numpy`` under ``jax.default_matmul_precision("highest")``: a
dense ``S × S`` mask per head, a Python loop over the held experts,
each run on every token and weighted by zero where it was not chosen;
no kernel, no ragged product, no sorting, no bf16.

It reads the program's parameter tree (the names are the interface)
and the program's spec kwargs: the published config's keys plus the
held share. Nothing else is shared: the rotary tables, the masks, the
router and the experts are written out again here.

The model, one row ``x [S, hidden]``::

    h = x + Attn_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))
    Attn_l: q = x Wq [S, H_l, d]; k, v = x Wk, x Wv [S, G, d]
            rotary on q, k; a_h = softmax(q_h k_g(h)^T / sqrt(d) + mask) v_g(h)
            g(h) = h // (H_l / G); mask causal, sliding layers also i - j < window
            out = concat_h(sigmoid(x Wgamma)_h * a_h) Wo
    dense FFN: (silu(x Wg) * x Wu) Wd
    sparse FFN: p = softmax(x Wr) over all experts; T = top-k(p)
            w_e = p_e / sum_{T} p;  out = scale * sum_{e in T} w_e E_e(x) + E_shared(x)
    head: RMSNorm, then W_head; loss: mean cross-entropy of the next id

Departures from the published description, each also under
``assumed`` in ``configs/laguna-s-2.1-ep16.json``:

* ``hidden_act`` is SiLU, the router scores by softmax, the gate is a
  sigmoid on each head's output before ``Wo``, there is no QK-norm
  and no router auxiliary loss: the config names the mechanisms and
  has no key for these, so they follow the family's convention;
* rotary pairs dimension ``i`` with ``i + dims/2``; YaRN's inverse
  frequencies and its attention factor on cos and sin are computed as
  the ``transformers`` library does;
* **the held share**: only ``layers_held`` layers, ``vocab_held``
  rows of the vocabulary and experts ``expert_offset ..
  expert_offset + experts_held - 1`` exist. The sum over a token's
  chosen experts runs over the held ones only; the router, its
  top-k and the renormalisation are over all ``num_experts``.

``blocks=True`` is for the chip, where one head's ``S × S`` scores
fit and a layer's do not: rows and heads are taken one at a time
(``lax.map``) and recomputed in the backward pass
(``jax.checkpoint``) instead of kept. The arithmetic is the same; a
test holds the two to each other.

``dtype=jnp.bfloat16`` is NOT the reference: it is the reading "what
if the float32 parts (router, softmax, loss, norms) were computed in
the compute type", which the tolerances below must refuse.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------- tolerances
#
# Each is set from two chip readings at the cell's sizes (one v5e,
# 2 × 8,192 positions; PERF.md §6, PR 26, has both): what the program
# gave over twenty-four seeds, and the control — this file with
# ``dtype=jnp.bfloat16`` (router, softmax, norms and loss in the
# compute type) put in the program's place and held to the float32
# reference by the driver's own ``verify``
# (``chipbench/lowered_reading.py``), four seeds — which has to come
# out not ``correct``. bf16 products with float32 accumulation through
# five layers of width 3,072 put ~2e-2 relative noise on a logit row
# and 4e-2 to 1.5e-1 on a leaf's gradient. Most is on the router's and
# the experts', where a top-10 choice that flips near a tie moves a
# whole token: the program's float32 router, fed bf16 activations,
# chooses otherwise than the reference in 1.8–2.4 % of a layer's
# token–expert pairs (``router_choice_flips``), the control in
# 3.0–3.8 %.

#: |loss − reference| / reference, first step. The precision hardly
#: moves it (program 1.7e-6 to 3.2e-5, control 3.6e-5 to 4.7e-5: a
#: mean over 16,384 positions), so no limit of its own can sit
#: between: it takes the accepted train cells'
#: (``reference.LOSS_TOLERANCE``), which leaves the first readings
#: over a hundred times of room and still refuses a wrong loss
from chipbench.reference import LOSS_TOLERANCE  # noqa: E402,F401

#: the sampled positions' logit rows, relative L2 error: the median
#: row (program 0.0191–0.0203; control 0.0305–0.0322), the limit at
#: the geometric middle. It alone refuses the control on every seed.
#: The worst row (0.045–0.074; control 0.066–0.085) is reported and
#: not limited: the largest of 64 swings with the sample
LOGITS_MEDIAN_TOLERANCE = 0.025
#: relative L2 error of (first-step update ÷ −lr) against the
#: reference's gradient beyond what storing the new weight in
#: float32 adds (:func:`update_error`), by the kind of leaf — the
#: program's readings, then the control's. The first two sit at the
#: geometric middle and each refuses the control on every seed. The
#: last two cannot: with another sampled expert or another seed the
#: control reads what the program reads elsewhere (the same flipped
#: choices, a little more often), so they are guards against a gross
#: fault — a dropped pair, a wrong expert's weight — with room over
#: the program's largest, and refuse the control on three seeds of
#: four:
GRAD_TOLERANCE = {
    "embed": 0.047,         # 0.035–0.040 | 0.055–0.057
    "attn": 0.066,          # 0.050–0.056 | 0.079–0.084
    "experts": 0.16,        # 0.090–0.134 | 0.134–0.169
    "router": 0.175,        # 0.130–0.149 | 0.167–0.185
}


def grad_tolerance(leaf: str) -> float:
    """The limit for a sampled leaf, by its path."""
    for kind, limit in GRAD_TOLERANCE.items():
        if kind in leaf:
            return limit
    raise KeyError(f"no tolerance for leaf {leaf!r}")


def _rms(x, scale, eps, dtype):
    x = x.astype(dtype)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(dtype))


def _inv_freq(r: dict, head_dim: int) -> np.ndarray:
    dims = int(head_dim * r.get("partial_rotary_factor", 1))
    exponent = np.arange(0, dims, 2, dtype=np.float64) / dims
    extrapolated = 1.0 / (float(r["rope_theta"]) ** exponent)
    if r.get("rope_type", "default") != "yarn":
        return extrapolated
    interpolated = extrapolated / r["factor"]
    original = r["original_max_position_embeddings"]

    def dim_of(turns):
        return (dims * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(r["rope_theta"])))

    low = max(math.floor(dim_of(r["beta_fast"])), 0)
    high = min(math.ceil(dim_of(r["beta_slow"])), dims - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dims // 2) - low) / (high - low), 0, 1)
    return interpolated * ramp + extrapolated * (1 - ramp)


def _rotary(x, r: dict):
    """``x [S, H, d]``: rotate the leading ``dims`` of every head."""
    inv = _inv_freq(r, x.shape[-1])
    dims = 2 * len(inv)
    angle = np.arange(x.shape[0])[:, None] * inv[None, :]
    factor = r.get("attention_factor", 1.0)
    cos = jnp.asarray(np.cos(angle) * factor, x.dtype)[:, None, :]
    sin = jnp.asarray(np.sin(angle) * factor, x.dtype)[:, None, :]
    first, second = x[..., :dims // 2], x[..., dims // 2:dims]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin,
                            x[..., dims:]], axis=-1)


def _head(q, k, v, window: int):
    """One head: ``q, k, v [S, d]`` under a dense ``S × S`` mask."""
    s_len, d = q.shape
    i = jnp.arange(s_len)[:, None]
    j = jnp.arange(s_len)[None, :]
    mask = j <= i
    if window:
        mask = mask & (i - j < window)
    scores = (q @ k.T) / math.sqrt(d)
    scores = jnp.where(mask, scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1) @ v


def attention(p: dict, x, heads: int, window: int, rope: dict, kw: dict,
              blocks: bool = False):
    """``x [S, hidden]`` (normed) → ``[S, hidden]``."""
    s_len = x.shape[0]
    g, d = kw["num_key_value_heads"], kw["head_dim"]
    w = {n: p[n].astype(x.dtype) for n in p}
    q = _rotary((x @ w["q_proj"]).reshape(s_len, heads, d), rope)
    k = _rotary((x @ w["k_proj"]).reshape(s_len, g, d), rope)
    v = (x @ w["v_proj"]).reshape(s_len, g, d)
    gate = jax.nn.sigmoid(x @ w["gate_proj"])           # [S, H]
    per_group = heads // g
    if blocks:
        a = jax.lax.map(
            jax.checkpoint(lambda t: _head(t[0], t[1], t[2], window)),
            (q.transpose(1, 0, 2),
             jnp.repeat(k.transpose(1, 0, 2), per_group, axis=0),
             jnp.repeat(v.transpose(1, 0, 2), per_group, axis=0)))
        a = a.transpose(1, 0, 2)
    else:
        a = jnp.stack([_head(q[:, h], k[:, h // per_group],
                             v[:, h // per_group], window)
                       for h in range(heads)], axis=1)
    a = a * gate[..., None]
    return a.reshape(s_len, heads * d) @ w["o_proj"]


def _mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def routed_weights(p: dict, x, kw: dict):
    """``[S, num_experts]``: each token's weight on every expert —
    the renormalised top-k of the softmax, zero elsewhere."""
    probs = jax.nn.softmax(x @ p["router"].astype(x.dtype), axis=-1)
    top, chosen = jax.lax.top_k(probs, kw["num_experts_per_tok"])
    if kw["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, chosen].set(top)


def sparse_ffn(p: dict, x, kw: dict, shared: bool = True, weights=None):
    """``x [S, hidden]`` (normed) → the held experts' weighted part of
    the routed result, scaled, plus (``shared``) the shared expert.
    ``weights``: :func:`routed_weights`, where the caller has them."""
    if weights is None:
        weights = routed_weights(p, x, kw)
    w = {n: p[n].astype(x.dtype) for n in
         ("experts_gate", "experts_up", "experts_down")}
    out = jnp.zeros_like(x)
    for e in range(kw["experts_held"]):
        out = out + weights[:, kw["expert_offset"] + e, None] * _mlp(
            x, w["experts_gate"][e], w["experts_up"][e],
            w["experts_down"][e])
    out = out * kw["moe_routed_scaling_factor"]
    if shared:
        s = {n: v.astype(x.dtype) for n, v in p["shared"].items()}
        out = out + _mlp(x, s["gate_proj"], s["up_proj"],
                         s["down_proj"])
    return out


def _row(params: dict, ids, kw: dict, blocks: bool, dtype):
    """One row: ids ``[S]`` → logits ``[S, vocab_held]`` and each
    sparse layer's chosen experts ``[layers, S, num_experts]``
    (bool)."""
    p = params["params"]
    eps = kw["rms_norm_eps"]
    x = p["embed"][ids].astype(dtype)
    chosen = []
    for i in range(kw["layers_held"]):
        kind = kw["layer_types"][i]

        def layer(x, lp, i=i, kind=kind):
            window = (kw["sliding_window"]
                      if kind == "sliding_attention" else 0)
            n = _rms(x, lp["input_norm"]["scale"], eps, dtype)
            h = x + attention(
                lp["attn"], n, kw["num_attention_heads_per_layer"][i],
                window, kw["rope_parameters"][kind], kw, blocks)
            n = _rms(h, lp["post_attn_norm"]["scale"], eps, dtype)
            if kw["mlp_layer_types"][i] == "sparse":
                weights = routed_weights(lp["ffn"], n, kw)
                return (h + sparse_ffn(lp["ffn"], n, kw,
                                       weights=weights), weights > 0)
            f = {k: v.astype(dtype) for k, v in lp["ffn"].items()}
            return h + _mlp(n, f["gate_proj"], f["up_proj"],
                            f["down_proj"]), None

        if blocks:
            layer = jax.checkpoint(layer)
        x, picked = layer(x, p[f"layer{i}"])
        if picked is not None:
            chosen.append(picked)
    n = _rms(x, p["norm"]["scale"], eps, dtype)
    if not chosen:
        chosen = [jnp.zeros((0, kw["num_experts"]), bool)]
    return n @ p["head"].astype(dtype), jnp.stack(chosen)


def forward(params: dict, ids, kw: dict, blocks: bool = False,
            dtype=jnp.float32, choices: bool = False):
    """ids ``[B, S]`` → logits ``[B, S, vocab_held]``; with
    ``choices`` also the experts each token chose, bool ``[sparse
    layers, B, S, num_experts]``."""
    with jax.default_matmul_precision("highest"):
        if blocks:
            logits, chosen = jax.lax.map(
                lambda row: _row(params, row, kw, True, dtype), ids)
        else:
            logits, chosen = (jnp.stack(x) for x in zip(*(
                _row(params, row, kw, False, dtype) for row in ids)))
    if choices:
        return logits, chosen.swapaxes(0, 1)
    return logits


def loss_of(logits, labels):
    """Mean cross-entropy of ``labels [B, S]`` under ``logits``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked.astype(jnp.float32))


def loss(params: dict, ids, labels, kw: dict, blocks: bool = False,
         dtype=jnp.float32):
    return loss_of(forward(params, ids, kw, blocks, dtype), labels)


def pick(params: dict, paths) -> dict:
    """The leaves named by ``paths`` (tuples of keys under
    ``params``), as ``{"/".join(path): leaf}``."""
    out = {}
    for path in paths:
        node = params["params"]
        for key in path:
            node = node[key]
        out["/".join(path)] = node
    return out


def put(params: dict, leaves: dict) -> dict:
    """``params`` with the leaves of :func:`pick` replaced."""
    def rebuild(node, prefix):
        if not isinstance(node, dict):
            return leaves.get(prefix, node)
        return {k: rebuild(v, f"{prefix}/{k}" if prefix else k)
                for k, v in node.items()}

    return {"params": rebuild(params["params"], "")}


def loss_and_grads(params: dict, ids, labels, kw: dict, paths=None,
                   blocks: bool = False, dtype=jnp.float32):
    """The loss and its gradient: with respect to every leaf, or to
    the leaves ``paths`` names only (the chip's sample: a gradient
    tree of the whole model is 4.5 GB beside 4.5 GB of weights)."""
    if paths is None:
        return jax.value_and_grad(loss)(params, ids, labels, kw,
                                        blocks, dtype)
    return jax.value_and_grad(
        lambda leaves: loss(put(params, leaves), ids, labels, kw,
                            blocks, dtype))(pick(params, paths))


def update_error(old, new, grad, lr: float) -> dict:
    """How far a leaf's first SGD update is from ``−lr × grad``.

    ``raw`` is ``‖(new − old) / −lr − grad‖ / ‖grad‖``. At this size
    it is mostly not the gradient's error: the mean loss over 16,384
    positions gives a weight of 0.02 a step of a few units in its
    last place, so storing ``new`` in float32 rounds the update by a
    tenth of itself. That rounding is known — it is what
    ``float32(old − lr·grad)`` loses — and independent of the
    gradient's own error, so its energy is taken off: ``excess`` is
    what is left, the number the tolerance is on. ``rounding`` is the
    part taken off, in the same units."""
    old64 = np.asarray(old, np.float64)
    grad64 = np.asarray(grad, np.float64)
    stepped = old64 - lr * grad64
    lost = stepped.astype(np.float32).astype(np.float64) - stepped
    got = (np.asarray(new, np.float64) - old64) / -lr
    norm = max(np.linalg.norm(grad64), 1e-300)
    raw = np.linalg.norm(got - grad64) / norm
    rounding = np.linalg.norm(lost) / lr / norm
    return {"raw": float(raw), "rounding": float(rounding),
            "excess": float(np.sqrt(max(raw ** 2 - rounding ** 2, 0)))}


def relative_error(got, want) -> float:
    """``‖got − want‖ / ‖want‖`` in float64 on the host."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-300))
