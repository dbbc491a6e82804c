"""Move-sequence policy: a causal decoder over the game record.

The SL stage learns "next expert move given what came before". The
conv policy reads what came before from 48 hand-made planes of one
position; this one reads the record itself. A token is a move (board
points ``0..size²-1``, pass ``size²``, a game separator ``size²+1``),
a row is several games packed end to end, the label at each token is
the next token, and a *position* is one token at which the next move
is predicted. Logits are ``[B, S, vocab_held]``.

The decoder is the published ``laguna`` block (poolside Laguna-S-2.1,
``config.json``), driven by that file's keys under their own names:

* pre-norm residual blocks, RMSNorm, no biases;
* grouped-query attention with a PER-LAYER query-head count
  (``num_attention_heads_per_layer``), a per-head sigmoid output gate
  (``gating: per-head``), and a layer pattern (``layer_types``) of
  full and sliding-window layers, each kind with its own rotary
  (``rope_parameters``: YaRN on half the head for full layers, plain
  RoPE on the whole head for sliding ones);
* a dense SwiGLU MLP where ``mlp_layer_types`` says ``dense`` and
  elsewhere ``num_experts`` routed SwiGLU experts, top
  ``num_experts_per_tok`` by a float32 softmax router, renormalised
  (``norm_topk_prob``), scaled (``moe_routed_scaling_factor``), plus
  one shared expert.

A second published block is read from its own keys beside it —
``xing4_0`` (XingChen-AGI Xing4.0-29B-A4B, ``config.json``; the
DeepSeek-V3 family's names) — and shares every line it can with the
first. A spec chooses by the keys it carries, nothing else does:

* ``kv_lora_rank``: **latent attention** (:class:`LatentAttention`):
  low-rank query and key/value paths with their own norms, per head a
  non-rotary part of ``qk_nope_head_dim`` and a rotary part of
  ``qk_rope_head_dim`` that every head's key shares, values of
  ``v_head_dim`` — query/key heads of 192 and value heads of 128 as
  published, run as so many heads with a key each (training: no
  absorbed form);
* ``scoring_func: sigmoid``: the router scores by a sigmoid, chooses
  by the score PLUS a bias that carries no gradient
  (``topk_method: noaux_tc``) and weighs by the scores without it;
* ``hc_mult``: **hyper-connections** in place of ``x + f(x)``
  (:class:`HyperConnection`; Zhu et al., arXiv:2409.19606, with the
  manifold constraint of arXiv:2512.24880): the residual state is
  ``hc_mult`` streams, each sublayer reads a learned per-token mix
  of them and writes back through a doubly-stochastic matrix made by
  ``hc_sinkhorn_iters`` Sinkhorn iterations;
* ``num_nextn_predict_layers: 1``: a **multi-token-prediction
  module** (DeepSeek-V3 report §2.2) — one more block on the trunk's
  output joined with the next id's embedding, predicting the id after
  next through the shared embedding and head — whenever the next ids
  are given (training; a forward pass without them has no use for it).

A third is read from the ``bailing_hybrid`` keys (inclusionAI
Ling-3.0-flash, ``config.json``), which are the second's where the
two overlap and add:

* ``layer_group_size``: a **hybrid pattern of mixer kinds** — layer
  ``i`` (by its published index) is latent attention where ``(i + 1)
  % layer_group_size == 0`` and **Kimi delta attention** elsewhere
  (:class:`KimiDeltaAttention`; Kimi Linear, arXiv:2510.26692, over
  the gated delta rule of arXiv:2412.06464): a token mixer that is a
  recurrence, per head a ``d_k × d_v`` state that decays per key
  channel and is corrected by a delta rule, run as a chunked scan
  (:func:`kda_chunked`);
* ``q_lora_rank: null``: latent attention without the low-rank query
  path, and ``gated_attention_proj_granularity_type: head_wise``: a
  sigmoid gate per head before its output projection;
* ``n_group`` / ``topk_group``: **group-limited choice** — the
  experts lie in ``n_group`` groups by index, a group scores the sum
  of its two best ``s + b``, and the choice is made among the
  ``topk_group`` best groups' experts (DeepSeek-V3's ``noaux_tc``);
* ``mtp_use_kda: false`` / ``mtp_loss_scaling_factor``: the
  multi-token-prediction block is a latent-attention + expert layer
  whatever the last layer is, and the module's loss weight is the
  model's own key (the published value is 0).

**The held share.** A spec also says what part of the model lives
here: ``layers_held`` leading layers, ``vocab_held`` leading rows of
the vocabulary (embedding, head, logits and loss are over the slice),
and ``experts_held`` routed experts starting at ``expert_offset``.
The router keeps its published width and its experts per token; the
expert layer computes its own experts' part of the result for the
tokens routed to them, and what the absent experts would add is left
out — that partial result goes on to the next layer. Nothing stands
in for the absent chips or their exchange.

**How it runs on the chip** (bf16 compute, float32 parameters, float32
router, softmax and loss):

* attention never holds an ``S × S`` array. On a TPU, at shapes its
  tiles divide, it is JAX's own block-sparse flash kernel (Pallas
  ``splash_attention``: grouped-query natively, a causal or a local
  mask per layer kind, fully masked blocks skipped, its own backward
  kernels) — :func:`kernel_attention`. Anywhere else (the CPU tests,
  a toy shape) it is plain XLA, one key/value group at a time
  (``lax.map``): causal query blocks over their key prefix (full
  layers; each block recomputed in the backward pass) or one banded
  product of every query block against itself and its predecessor
  (sliding layers, ``block = window``) — :func:`grouped_attention`.
  On the v5e the XLA form spent 3.2 s a step in its softmax's lane
  reductions (PERF.md, PR 26), which is why the kernel is there;
* the expert product is ``jax.lax.ragged_dot`` over token–expert
  pairs sorted by held expert — a grouped matrix product over a
  ragged split. The pairs sent here are sorted to the front of a
  buffer that holds EVERY pair a chunk of tokens could send
  (``chunk × top_k`` rows), so no pair is dropped however unbalanced
  the routing; rows past the held pairs are skipped by the product,
  and every other pass of the layer — the dispatch gather, SwiGLU's
  inside between the products, the weighted sum of the results by
  token, and their mirrors in the backward pass — walks the buffer
  in row blocks and only the blocks that hold a pair
  (:func:`_held_blocks`); what belongs to a token is added to the
  token's row from the block that holds the pair, so nothing is
  ever gathered or laid out a row for every pair a chunk could
  send. The work follows what arrives, three blocks of forty on
  balanced traffic and all forty when every pair lands here, one
  body run more or fewer times and no second path. What the buffer
  left out is counted from the buffer (``moe_dropped``), not
  assumed, and so are the blocks that ran;
* in a delta layer, what lies between a projection's product and
  the scan's ``q``, ``k`` or ``v`` — causal convolution, SiLU, the L2
  norm over a head, the cast — is one float32 pass over the bf16
  product each way (:func:`kda_mixed`): on a TPU at whole tiles two
  Pallas kernels, a block of rows with a halo of sixteen, the result
  written once in the scan's own chunk-major order; anywhere else the same algorithm as XLA writes it
  (:func:`causal_conv`, :func:`l2_normed`: the definition). Its
  backward keeps the product and the taps alone and recomputes the
  rest in VMEM. In XLA alone the same work moved fourteen times its
  bytes (PERF.md, PR 34 and PR 35);
* every layer is recomputed in the backward pass (``nn.remat``),
  all of it but the attention kernel: what a step keeps is one
  ``[B, S, hidden]`` input per layer and, where the kernel runs, its
  output ``[B, H, S, dv]`` and row statistics ``[B, H, S]``, which
  carry the name :data:`KERNEL_RESIDUALS` — so the kernel's forward
  runs once a step and its backward reads what that call left. The
  XLA form has no such name and keeps nothing more.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from rocalphago_tpu.models.nn_util import NeuralNetBase, neuralnet
from rocalphago_tpu.obs import scopes

#: masked scores: far below any real one, and finite
NEG = -1e30
#: the parts of a step's returned metrics that count routing
MOE_STATS = ("moe_routed", "moe_held", "moe_dropped", "moe_load_max",
             "moe_row_blocks_run", "moe_row_blocks")


class Rope(NamedTuple):
    """One layer kind's rotary embedding, as ``rope_parameters`` has
    it: ``dims`` leading dimensions of the head are rotated."""

    kind: str               # "default" | "yarn"
    theta: float
    dims: int
    factor: float = 1.0
    original: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


class Latent(NamedTuple):
    """Latent attention's sizes, as the config names them."""

    q_rank: int             # q_lora_rank; 0 (null): no low-rank path
    kv_rank: int            # kv_lora_rank
    nope: int               # qk_nope_head_dim
    rope: int               # qk_rope_head_dim
    value: int              # v_head_dim
    scale: float            # of the scores: 1/sqrt(nope + rope) x mscale^2
    gate: bool = False      # a head-wise sigmoid gate before o_proj


class Kda(NamedTuple):
    """Kimi delta attention's sizes and constants, as the config
    names them."""

    key: int                # head_dim: a head's queries and keys
    value: int              # head_dim: a head's values
    conv: int               # short_conv_kernel_size
    lower: float            # kda_lower_bound: the log-decay's floor


class Hyper(NamedTuple):
    """The hyper-connections' constants (``hc_*``, ``mhc_*``)."""

    streams: int            # hc_mult
    iters: int              # hc_sinkhorn_iters
    eps: float              # hc_eps: Sinkhorn's denominator guard
    clamp: tuple            # (mhc_h_res_clamp_min, _max)


class LayerSpec(NamedTuple):
    heads: int              # query heads of this layer
    window: int             # 0 = full attention
    rope: Rope
    sparse: bool            # routed experts (else the dense MLP)
    latent: Latent | None = None    # latent attention (else gated GQA)
    kda: Kda | None = None          # Kimi delta attention (else either)


def rope_inv_freq(rope: Rope) -> np.ndarray:
    """Inverse frequencies ``[dims / 2]``. YaRN blends interpolated
    and extrapolated frequencies by a linear ramp between the
    dimensions that turn ``beta_fast`` and ``beta_slow`` times over
    the original context (Peng et al. 2023, as ``transformers``
    computes it)."""
    dims = rope.dims
    pos = rope.theta ** (np.arange(0, dims, 2, dtype=np.float64) / dims)
    if rope.kind == "default":
        return (1.0 / pos).astype(np.float32)

    def correction_dim(turns: float) -> float:
        return (dims * math.log(rope.original / (turns * 2 * math.pi))
                / (2 * math.log(rope.theta)))

    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), dims - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dims // 2) - low) / (high - low), 0, 1)
    extrapolation = 1.0 - ramp
    inv = (1.0 / (rope.factor * pos)) * (1 - extrapolation) \
        + (1.0 / pos) * extrapolation
    return inv.astype(np.float32)


def apply_rope(x: jax.Array, rope: Rope, scale: float = 1.0
               ) -> jax.Array:
    """Rotate the leading ``rope.dims`` of each head of ``x``
    ``[B, S, H, head_dim]`` by position (half-split pairing:
    dimension ``i`` with ``i + dims/2``), in float32; ``scale``
    (the queries' ``1/√d``) is applied before the cast back."""
    d = rope.dims
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    angle = pos[:, None] * jnp.asarray(rope_inv_freq(rope))[None, :]
    cos = (jnp.cos(angle) * rope.attention_factor)[None, :, None, :]
    sin = (jnp.sin(angle) * rope.attention_factor)[None, :, None, :]
    xf = x.astype(jnp.float32)
    a, b, rest = xf[..., :d // 2], xf[..., d // 2:d], xf[..., d:]
    out = jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, rest], axis=-1)
    return (out * scale).astype(x.dtype)


# ------------------------------------------------------------ attention
#
# Both forms take queries already scaled by 1/√d.

#: tile of the attention kernel: query and key blocks, forward and
#: backward (MaxText's choice for the v5e)
KERNEL_BLOCK = 512
#: query block of the XLA form's full layers (a shorter row is one
#: block)
ATTENTION_BLOCK = 1024
#: the name the kernel's output and row statistics carry
#: (``jax.ad_checkpoint.checkpoint_name``), and the one thing a
#: layer's recomputation keeps
KERNEL_RESIDUALS = "seq_attn_kernel_residuals"


def kernel_platform() -> str:
    """The platform whose attention the trace is for."""
    return jax.default_backend()


def use_kernel(s_len: int, *head_dims: int) -> bool:
    """Whether the kernel takes these heads: whole 128-lane tiles, or
    whole tiles and a half (latent attention's 192: Mosaic compiles
    it for the v5e and the chip agrees with the reference, PERF.md,
    PR 30)."""
    return (kernel_platform() == "tpu" and s_len % KERNEL_BLOCK == 0
            and all(d >= 128 and d % 64 == 0 for d in head_dims))


def kernel_attention(q, k, v, window: int, interpret: bool = False):
    """Causal grouped-query attention by the splash kernel:
    ``q [B, S, H, d]``, ``k [B, S, G, d]``, ``v [B, S, G, dv]`` →
    ``[B, S, H, dv]``. ``window`` 0 is full attention, else
    ``i − j < window``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as masks,
    )

    s_len, h = q.shape[1], q.shape[2]
    one = (masks.LocalMask((s_len, s_len), (window - 1, 0), 0)
           if window and window < s_len
           else masks.CausalMask((s_len, s_len)))
    b = KERNEL_BLOCK
    kernel = splash.make_splash_mha_single_device(
        masks.MultiHeadMask([one] * h),
        block_sizes=splash.BlockSizes(
            block_q=b, block_kv=b, block_kv_compute=b, block_q_dkv=b,
            block_kv_dkv=b, block_kv_dkv_compute=b, block_q_dq=b,
            block_kv_dq=b),
        residual_checkpoint_name=KERNEL_RESIDUALS,
        interpret=interpret)
    with jax.named_scope(scopes.SEQ_ATTN_KERNEL):
        out = jax.vmap(kernel)(*(x.transpose(0, 2, 1, 3)
                                 for x in (q, k, v)))
    return out.transpose(0, 2, 1, 3)


def _softmax_pv(s: jax.Array, mask: jax.Array, v: jax.Array,
                spec: str) -> jax.Array:
    """float32 softmax of masked scores, then the product with
    ``v`` in ``v``'s type."""
    s = jnp.where(mask, s, NEG)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    return jnp.einsum(spec, p.astype(v.dtype), v)


def _prefix_block(q, k, v, q0: int):
    """Causal attention of one query block ``[R, bq, d]`` at offset
    ``q0`` over its key prefix ``[L, d]``."""
    s = jnp.einsum("rqd,kd->rqk", q, k,
                   preferred_element_type=jnp.float32)
    qpos = q0 + jnp.arange(q.shape[1])
    mask = qpos[:, None] >= jnp.arange(k.shape[0])[None, :]
    return _softmax_pv(s, mask[None], v, "rqk,kd->rqd")


def _group_full(q, k, v):
    """One key/value group, causal: ``q [R, S, d]``, ``k, v [S, d]``.
    Query blocks over their key prefixes — about half the products of
    the square — each recomputed in the backward pass, so that one
    block's scores are all that ever exists."""
    s_len = q.shape[1]
    bq = min(ATTENTION_BLOCK, s_len)
    if s_len % bq:
        raise ValueError(f"sequence {s_len} is not whole blocks of {bq}")
    outs = []
    for i in range(s_len // bq):
        hi = (i + 1) * bq
        outs.append(jax.checkpoint(
            functools.partial(_prefix_block, q0=i * bq))(
                q[:, i * bq:hi], k[:hi], v[:hi]))
    return jnp.concatenate(outs, axis=1)


def _group_window(q, k, v, window: int):
    """One key/value group, causal within ``window`` (``i − j <
    window``): every query block of ``window`` positions against
    itself and the block before it, as one banded product."""
    r, s_len, d = q.shape
    nb = s_len // window
    if s_len % window:
        raise ValueError(
            f"sequence {s_len} is not whole windows of {window}")
    qb = q.reshape(r, nb, window, d)

    def with_previous(x):
        xb = x.reshape(nb, window, x.shape[-1])
        prev = jnp.concatenate([jnp.zeros_like(xb[:1]), xb[:-1]])
        return jnp.concatenate([prev, xb], axis=1)     # [nb, 2w, d]

    k2, v2 = with_previous(k), with_previous(v)
    s = jnp.einsum("rnqd,nkd->rnqk", qb, k2,
                   preferred_element_type=jnp.float32)
    qq = jnp.arange(window)[:, None]
    kk = jnp.arange(2 * window)[None, :]
    # key kk of the pair is position kk − window relative to the
    # block: causal kk − window ≤ qq, in the window kk > qq, and the
    # first block has no predecessor
    band = (kk <= qq + window) & (kk > qq)
    real = (jnp.arange(nb)[:, None, None] > 0) | (kk >= window)[None]
    out = _softmax_pv(s, (band[None] & real)[None], v2,
                      "rnqk,nkd->rnqd")
    return out.reshape(r, s_len, v.shape[-1])


def grouped_attention(q, k, v, window: int):
    """Causal grouped-query attention without an ``S × S`` array, in
    plain XLA: ``q [B, S, H, d]``, ``k [B, S, G, d]``, ``v [B, S, G,
    dv]`` → ``[B, S, H, dv]``. ``window`` 0 (or one that covers the
    sequence) is full attention."""
    b, s_len, h, d = q.shape
    g, dv = k.shape[2], v.shape[3]
    r = h // g
    qg = q.reshape(b, s_len, g, r, d).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(b * g, r, s_len, d)
    kg = k.transpose(0, 2, 1, 3).reshape(b * g, s_len, d)
    vg = v.transpose(0, 2, 1, 3).reshape(b * g, s_len, dv)
    if window and window < s_len:
        one = functools.partial(_group_window, window=window)
    else:
        one = _group_full
    # a group's scores are recomputed in the backward pass, not kept
    # for every group at once
    out = jax.lax.map(jax.checkpoint(lambda a: one(*a)), (qg, kg, vg))
    out = out.reshape(b, g, r, s_len, dv).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, s_len, h, dv)


def _attention(q, k, v, window: int):
    """The kernel where its tiles fit, else the XLA form."""
    if use_kernel(q.shape[1], q.shape[3], v.shape[3]):
        return kernel_attention(q, k, v, window)
    return grouped_attention(q, k, v, window)


# ------------------------------------------------- Kimi delta attention
#
# Per head a state ``S [d_k, d_v]``, zero at the row's start:
#
#     S_t = (I − β_t k_t k_tᵀ) Diag(exp g_t) S_{t−1} + β_t k_t v_tᵀ
#     o_t = S_tᵀ q_t
#
# with a log-decay ``g_t ∈ (lower, 0)`` per key channel. The chunkwise
# form (Kimi Linear, arXiv:2510.26692 §3; ``G_r = Σ_{i≤r} g_i`` inside
# a chunk of ``C`` tokens, ``S`` the state that enters it):
#
#     A = strict_lower(Diag(β) (K ⊙ e^G) (K ⊙ e^−G)ᵀ)
#     T = (I + A)^−1 Diag(β);  W = T (K ⊙ e^G);  U = T V
#     V' = U − W S
#     O  = (Q ⊙ e^G) S + lower((Q ⊙ e^G) (K ⊙ e^−G)ᵀ) V'
#     S ← Diag(e^{G_C}) S + (K ⊙ e^{G_C − G})ᵀ V'
#
# Everything but the last three lines is the same work for every
# chunk and runs for all of them at once, on arrays ``[N, B, H, C,
# ·]`` — the chunks' axis first from the first reshape, which is how
# the loop reads them; the last three are a ``lax.scan`` over the
# chunks. ``e^−G`` reaches ``e^{5C}`` and float32 ends at ``e^88``,
# so the two pairwise products are taken sixteen rows at a time
# relative to the first of them: rows scaled by ``e^{G_r − G_0} ≤
# 1``, columns by ``e^{G_0 − G_c}``, which is at most ``e^{5·15}`` on
# the rows' own sixteen and at most 1 before them — what the config's
# lower bound of −5 is for. The ``C / 16`` blocks of rows are a BATCH
# axis of one product, each block against all ``C`` columns: no
# slice, pad or concatenation, whose cotangents are pads and slices
# again. On the columns after a block ``G_0 − G_c`` has no bound, so
# there the EXPONENT is set to 0 before the exponential is taken
# (the column enters as plain ``k``): those entries lie above the
# diagonal, the mask ``r ≥ c`` discards them, and neither they nor
# their gradient ever hold an overflow. Exact: nothing is clamped.
# The products' backward (:func:`_pairwise_decayed_bwd`) keeps ``q,
# k, G`` alone and takes the exponentials again; towards the columns
# it is the same trick from the other side — the blocks of COLUMNS a
# batch axis, each against all ``C`` rows relative to the block's
# last column.

#: tokens of a chunk of the scan (the published kernels' 64; a
#: shorter row is one chunk)
KDA_CHUNK = 64
#: rows of a chunk whose pairwise decays share one point of
#: reference: ``|lower| × (KDA_SUB − 1)`` must stay under float32's
#: ``e^88`` (5 × 15 = 75)
KDA_SUB = 16
#: of the small float32 products the rest is built on — the pairwise
#: decays and the inverse: the MXU's full float32, as the router's
KDA_EXACT = jax.lax.Precision.HIGHEST


@jax.custom_vjp
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^−1`` of strictly lower triangular ``a [..., C, C]``:
    ``a`` is nilpotent, so the inverse is ``Σ (−a)^i = (I − a)(I +
    a²)(I + a⁴)…`` — ``log₂ C`` squarings and as many products, all
    of them matrix products over every chunk and head at once."""
    c = a.shape[-1]
    x, power, covered = jnp.eye(c, dtype=a.dtype) - a, a, 2
    while covered < c:
        power = jnp.matmul(power, power, precision=KDA_EXACT)
        x = x + jnp.matmul(x, power, precision=KDA_EXACT)
        covered *= 2
    return x


def _unit_lower_inverse_fwd(a):
    x = unit_lower_inverse(a)
    return x, x


def _unit_lower_inverse_bwd(x, d):
    """``d(M^−1) = −M^−1 dM M^−1``: the inverse is all it keeps."""
    xt = jnp.swapaxes(x, -1, -2)
    return (-jnp.matmul(jnp.matmul(xt, d, precision=KDA_EXACT), xt,
                        precision=KDA_EXACT),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd,
                          _unit_lower_inverse_bwd)


def _blocks(gc, sub: int):
    """``gc [..., C, d]`` as ``[..., C // sub, sub, d]``, and for
    block ``j`` (axis −3) a mask ``[C // sub, C, 1]`` of the positions
    of the chunk before the block's end, and one of those from its
    start on."""
    *lead, c, d = gc.shape
    nb = c // sub
    at = jnp.arange(c)[:, None]
    start = (jnp.arange(nb) * sub)[:, None, None]
    return gc.reshape(*lead, nb, sub, d), at < start + sub, at >= start


def _decayed_back(k, gc, g4, upto):
    """The columns for each block of rows ``[..., C // sub, C, d]``:
    ``k_c e^{G_0 − G_c}`` relative to the block's first row on the
    columns up to the block's end, plain ``k_c`` after it."""
    return k[..., None, :, :] * jnp.exp(jnp.where(
        upto, g4[..., :1, :] - gc[..., None, :, :], 0.0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pairwise_decayed(q, k, gc, sub: int):
    """``Σ_d x_{r,d} k_{c,d} e^{G_{r,d} − G_{c,d}}`` for ``x`` = ``q``
    and ``x`` = ``k``, ``[..., C, C]`` each, right wherever ``r ≥ c``
    (finite elsewhere, and masked by the caller): ``q, k, gc [..., C,
    d_k]`` float32, ``gc`` the inclusive sums of the log-decay inside
    the chunk. The ``C // sub`` blocks of ``sub`` rows are a batch
    axis of one product against all ``C`` columns, rows and columns
    scaled relative to the first of the rows (module comment)."""
    *lead, c, _ = gc.shape
    g4, upto, _ = _blocks(gc, sub)
    into = jnp.exp(g4 - g4[..., :1, :])                 # ≤ 1
    back = _decayed_back(k, gc, g4, upto)

    def against(x):
        return jnp.einsum("...jrd,...jcd->...jrc",
                          x.reshape(g4.shape) * into, back,
                          precision=KDA_EXACT).reshape(*lead, c, c)

    return against(q), against(k)


def _pairwise_decayed_fwd(q, k, gc, sub):
    return _pairwise_decayed(q, k, gc, sub), (q, k, gc)


def _pairwise_decayed_bwd(sub, kept, cotangents):
    """With ``D`` a product's cotangent where ``r ≥ c`` and 0 above:
    ``dx_r = Σ_c D_{rc} k_c e^{G_r − G_c}`` is the forward's product
    with ``D`` in the rows' place; the columns' ``dk_c = Σ_r D_{rc}
    x_r e^{G_r − G_c}`` takes the blocks of columns as the batch axis,
    relative to each block's LAST column — columns × ``e^{G_last −
    G_c} ≤ 1``, rows × ``e^{G_r − G_last}``: at most ``e^{5·15}``
    inside the block, at most 1 after it and the exponent zeroed
    before it, where ``D`` is 0; and ``dG = q dq + k (dk_rows −
    dk_columns)`` elementwise. No ``[..., C // sub, C, d]`` array is
    kept or summed over its blocks."""
    q, k, gc = kept
    *lead, c, _ = gc.shape
    nb = c // sub
    g4, upto, since = _blocks(gc, sub)
    lower = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    d_q, d_k = (jnp.where(lower, x, 0.0) for x in cotangents)
    into = jnp.exp(g4 - g4[..., :1, :])
    back = _decayed_back(k, gc, g4, upto)

    def to_rows(da):
        return (into * jnp.einsum(
            "...jrc,...jcd->...jrd", da.reshape(*lead, nb, sub, c), back,
            precision=KDA_EXACT)).reshape(gc.shape)

    last = g4[..., -1:, :]
    forth = jnp.exp(jnp.where(since, gc[..., None, :, :] - last, 0.0))

    def to_columns(da, x):
        da = jnp.moveaxis(da.reshape(*lead, c, nb, sub), -3, -2)
        return jnp.einsum("...irc,...ird->...icd", da,
                          x[..., None, :, :] * forth,
                          precision=KDA_EXACT)

    dq, dk_rows = to_rows(d_q), to_rows(d_k)
    dk_columns = (jnp.exp(last - g4) * (
        to_columns(d_q, q) + to_columns(d_k, k))).reshape(gc.shape)
    return (dq, dk_rows + dk_columns,
            q * dq + k * (dk_rows - dk_columns))


_pairwise_decayed.defvjp(_pairwise_decayed_fwd, _pairwise_decayed_bwd)


def _chunk_major(x, chunk: int):
    """``[B, S, H, d]`` → ``[N, B, H, C, d]`` in its own type: the
    order the scan works in."""
    b, s_len, h, w = x.shape
    return x.reshape(b, s_len // chunk, chunk, h, w).transpose(
        1, 0, 3, 2, 4)


def kda_chunked(q, k, v, g, beta, dtype=jnp.bfloat16):
    """The recurrence above in its chunkwise form: ``q, k [B, S, H,
    d_k]`` (normed, ``q`` scaled), ``v [B, S, H, d_v]``, the float32
    log-decay ``g [B, S, H, d_k]`` and ``beta [B, S, H]`` → float32
    ``o [B, S, H, d_v]``. The log-decay, its sums and exponentials
    and the carried state are float32; the products with the state
    take their operands in ``dtype`` (every one of them at most 1 in
    size: no ``e^−G`` among them) and add in float32. Every
    intra-chunk array is chunk-major, ``[N, B, H, C, ·]``: moved
    there once in its storage type, and the result moved back once;
    the pairwise decays' blocks of rows are a batch axis (module
    comment)."""
    b, s_len, h, dk = q.shape
    dv = v.shape[-1]
    c = min(KDA_CHUNK, s_len)
    sub = min(KDA_SUB, c)
    if s_len % c or c % sub:
        raise ValueError(f"a row of {s_len} is not whole chunks of {c} "
                         f"in blocks of {sub}")
    f32 = jnp.float32
    chunks = functools.partial(_chunk_major, chunk=c)
    # q and k enter float32 once, so their cotangents add up in
    # float32; v meets only a product that takes it in ``dtype``
    qc, kc, vc = chunks(q).astype(f32), chunks(k).astype(f32), chunks(v)
    gc = jnp.cumsum(chunks(g.astype(f32)), axis=3)
    bc = chunks(beta.astype(f32)[..., None])            # [N, B, H, C, 1]
    a_q, a_k = _pairwise_decayed(qc, kc, gc, sub)
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    a_q = jnp.where(row >= col, a_q, 0.0)
    t = unit_lower_inverse(jnp.where(row > col, bc * a_k, 0.0))
    t = t * jnp.swapaxes(bc, -1, -2)                    # T Diag(β)
    grown = jnp.exp(gc)                                 # e^G ≤ 1
    last = gc[..., -1:, :]

    def product(spec, x, y):
        return jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                          preferred_element_type=f32)

    # the loop's operands: its products' in the compute type once,
    # not once a turn
    per_chunk = (
        product("...rc,...cd->...rd", t, kc * grown).astype(dtype),
        product("...rc,...cd->...rd", t, vc),
        (qc * grown).astype(dtype), a_q.astype(dtype),
        (kc * jnp.exp(last - gc)).astype(dtype),
        jnp.exp(last[..., 0, :]))

    def step(state, x):
        w, u, q_in, a_q, k_out, decay = x
        fresh = u - product("bhck,bhkv->bhcv", w, state)
        out = (product("bhck,bhkv->bhcv", q_in, state)
               + product("bhcr,bhrv->bhcv", a_q, fresh))
        state = (decay[..., None] * state
                 + product("bhck,bhcv->bhkv", k_out, fresh))
        return state, out

    _, out = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32),
                          per_chunk)
    # [N, B, H, C, d_v] → [B, S, H, d_v]
    return out.transpose(1, 0, 3, 2, 4).reshape(b, s_len, h, dv)


def causal_conv(x: jax.Array, taps: jax.Array) -> jax.Array:
    """Depthwise causal convolution along the row, no bias, zero
    history at the row's start: ``x [B, S, C]``, ``taps [K, C]`` →
    float32 ``y_t = Σ_j taps[j] · x_{t − (K−1) + j}``."""
    k, s_len = taps.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    return sum(taps[j].astype(jnp.float32) * xp[:, j:j + s_len]
               for j in range(k))


#: ε under the L2 norms' root (``fla``'s)
L2_EPS = 1e-6


def l2_normed(x: jax.Array, eps: float = L2_EPS) -> jax.Array:
    """``x / ‖x‖₂`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


# ------------------- from a projection's product to the scan's operand
#
# What lies between ``y = x W`` ``[B, S, H·w]`` and the scan's ``q``,
# ``k`` or ``v`` — the causal convolution, SiLU, for q and k the L2
# norm over a head and a divisor — is one function, :func:`kda_mixed`,
# with a backward of its own that keeps ``y`` and the taps ALONE and
# recomputes the rest: with ``c`` the convolution, ``a = silu(c)``,
# ``n = a r`` with ``r = (Σ a² + ε)^−½`` over a head and ``o = n / ν``,
#
#     da = (r / ν) (do − n Σ n·do)          (``da = do`` without a norm)
#     dc = da · σ(c) (1 + c (1 − σ(c)))
#     dy_t = Σ_j taps[j] · dc_{t + (K−1) − j};  dtaps[j] = Σ_t dc_t y_{t − (K−1) + j}
#
# It has two lowerings of that one algorithm. :func:`causal_conv` and
# :func:`l2_normed` are its definition and what runs anywhere
# (:func:`_mixed_by_definition`, differentiated by JAX from ``y``
# again). On a TPU at whole tiles (:func:`use_mixing_kernel`) two
# Pallas kernels move a tensor through HBM once each way: a block of
# ``KDA_MIX_ROWS`` rows × ``KDA_MIX_LANES`` lanes of ``y`` as the
# product leaves it — S on the sublanes, a head its own lane tiles —
# with the ``KDA_MIX_HALO`` rows before it through a ``BlockSpec`` of
# their own (zeros at a row's start), everything in float32 in VMEM,
# shifts along the sublanes by ``pltpu.roll``, and the result written
# once, CHUNK-MAJOR ``[N, B, H, C, w]`` — the order the scan moves its
# operands to at once (:func:`_chunk_major`), S still on the sublanes,
# so a permutation of whole tiles inside the kernel; the transposition
# back to the signature's ``[B, S, H, w]`` and the scan's own cancel
# in XLA, and the scan's arrays lie as they always did. (Written
# head-major ``[B, H, S, w]`` XLA made both transpositions bitcasts by
# giving the scan's arrays THAT layout, and the scan read 13 ms a
# step slower: PERF.md, PR 35.) The backward kernel reads ``y`` with a
# halo on both sides and the cotangent, chunk-major too, with the
# rows after the block, recomputes ``dc`` on ``rows + halo`` rows
# (never written out), and writes ``dy`` once and a block's share of
# ``dtaps``, summed over the blocks by XLA.

#: rows, lanes and halo rows of a block of the mixing kernels (a bf16
#: tile is 16 rows; the halo must hold the ``K − 1`` rows a tap sees)
KDA_MIX_ROWS = 512
KDA_MIX_LANES = 512
KDA_MIX_HALO = 16


def use_mixing_kernel(y, taps, heads: int) -> bool:
    """Whether the mixing kernels take ``y [B, S, H·w]`` and ``taps
    [K, H·w]``: on a TPU, a head whole 128-lane tiles, the row whole
    blocks of rows."""
    _, s_len, width = y.shape
    head = width // heads
    return (kernel_platform() == "tpu" and head % 128 == 0
            and width % KDA_MIX_LANES == 0 and KDA_MIX_LANES % head == 0
            and s_len % KDA_MIX_ROWS == 0
            and KDA_MIX_ROWS % KDA_CHUNK == 0
            and KDA_CHUNK % KDA_MIX_HALO == 0
            and taps.shape[0] <= KDA_MIX_HALO)


def _mixed_by_definition(y, taps, heads: int, norm):
    b, s_len, width = y.shape
    a = jax.nn.silu(causal_conv(y, taps)).reshape(
        b, s_len, heads, width // heads)
    if norm is not None:
        a = l2_normed(a) / norm
    return a.astype(y.dtype)


def _shifted(x: jax.Array, by: int) -> jax.Array:
    """Rows moved down by ``by`` (row ``r`` holds what row ``r − by``
    held; the first ``by`` rows hold the last ones)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, by % x.shape[0], 0) if by else x


def _mix_forward_kernel(before_ref, y_ref, taps_ref, o_ref, *, norm):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    halo, (chunk, head) = before_ref.shape[1], o_ref.shape[3:]
    taps = taps_ref[...]
    k = taps.shape[0]
    # the block's rows behind the halo before them (zeros at a row's
    # start)
    before = jnp.where(pl.program_id(1) > 0, before_ref[0].astype(f32),
                       0.0)
    rows = jnp.concatenate([before, y_ref[0].astype(f32)], axis=0)
    c = sum(taps[j:j + 1] * _shifted(rows, k - 1 - j)
            for j in range(k))[halo:]
    a = jax.nn.silu(c)
    for i in range(o_ref.shape[2]):
        a_h = a[:, i * head:(i + 1) * head]
        if norm is not None:
            a_h = a_h * (jax.lax.rsqrt(
                (a_h * a_h).sum(-1, keepdims=True) + L2_EPS) / norm)
        for n in range(o_ref.shape[0]):
            o_ref[n, 0, i] = a_h[n * chunk:(n + 1) * chunk].astype(
                o_ref.dtype)


def _mix_backward_kernel(before_ref, y_ref, after_ref, d_ref,
                         d_after_ref, taps_ref, dy_ref, dtaps_ref, *,
                         norm):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    halo, head = before_ref.shape[1], d_ref.shape[4]
    n_rows = y_ref.shape[1]
    taps = taps_ref[...]
    k = taps.shape[0]
    at = pl.program_id(1)
    before = jnp.where(at > 0, before_ref[0].astype(f32), 0.0)
    rows = jnp.concatenate(
        [before, y_ref[0].astype(f32), after_ref[0].astype(f32)], axis=0)
    seen = [_shifted(rows, k - 1 - j) for j in range(k)]
    # the block's rows and the halo after them
    c = sum(taps[j:j + 1] * seen[j] for j in range(k))[halo:]
    gate = jax.nn.sigmoid(c)
    a = c * gate
    da = []
    for i in range(d_ref.shape[2]):
        d_h = jnp.concatenate(
            [d_ref[n, 0, i] for n in range(d_ref.shape[0])]
            + [d_after_ref[0, 0, i]], axis=0).astype(f32)
        if norm is not None:
            a_h = a[:, i * head:(i + 1) * head]
            r = jax.lax.rsqrt((a_h * a_h).sum(-1, keepdims=True) + L2_EPS)
            n_h = a_h * r
            d_h = (r / norm) * (
                d_h - n_h * (n_h * d_h).sum(-1, keepdims=True))
        da.append(d_h)
    dc = jnp.concatenate(da, axis=1) * (gate * (1.0 + c * (1.0 - gate)))
    # past the row's end there is no position
    row = jax.lax.broadcasted_iota(jnp.int32, (dc.shape[0], 1), 0)
    dc = jnp.where((row < n_rows) | (at < pl.num_programs(1) - 1),
                   dc, 0.0)
    dy_ref[0] = sum(taps[j:j + 1] * _shifted(dc, -(k - 1 - j))
                    for j in range(k))[:n_rows].astype(dy_ref.dtype)
    dtaps_ref[0, 0] = jnp.concatenate(
        [(dc[:n_rows] * seen[j][halo:halo + n_rows]).sum(0, keepdims=True)
         for j in range(k)], axis=0)


def _mix_specs(y, taps, head: int, rows: int, lanes: int, chunk: int):
    """What the two kernels share: the grid (batch row, block of rows,
    block of lanes), the taps' spec, and the makers of a ``BlockSpec``
    of ``n`` rows of ``y`` and of ``n`` rows of a chunk-major array
    ``[N, B, H, C, w]`` (whole chunks, or the first rows of one) —
    the grid's own block (``side`` 0), or the ``n`` rows before it
    (−1) or after it (+1), held inside the row: the kernels mask what
    that repeats."""
    from jax.experimental import pallas as pl

    b, s_len, width = y.shape

    def at(side: int, unit: int):
        """A grid block's index → the index of a block of ``unit``
        rows."""
        if not side:
            return lambda i: i
        per, last = rows // unit, s_len // unit - 1
        if side < 0:
            return lambda i: jnp.maximum(i * per - 1, 0)
        return lambda i: jnp.minimum((i + 1) * per, last)

    def y_rows(n, side=0):
        to = at(side, n)
        return pl.BlockSpec((1, n, lanes), lambda b, i, j: (b, to(i), j))

    def chunk_rows(n, side=0):
        to = at(side, max(n, chunk))
        return pl.BlockSpec(
            (max(n // chunk, 1), 1, lanes // head, min(n, chunk), head),
            lambda b, i, j: (to(i), b, j, 0, 0))

    taps_spec = pl.BlockSpec((taps.shape[0], lanes),
                             lambda b, i, j: (0, j))
    return ((b, s_len // rows, width // lanes), taps_spec, y_rows,
            chunk_rows)


def _mix_call(kernel, y, name: str, passes: int, ops: int, **kw):
    """``pallas_call`` as both kernels make it; for XLA's scheduler,
    ``passes`` arrays of ``y``'s size through HBM and about ``ops``
    float32 operations an element."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel, name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=64 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(
            flops=ops * y.size, transcendentals=y.size,
            bytes_accessed=passes * y.size * y.dtype.itemsize), **kw)


def mixing_kernel_forward(y, taps, heads: int, norm, *,
                          rows: int = KDA_MIX_ROWS,
                          lanes: int = KDA_MIX_LANES,
                          chunk: int = KDA_CHUNK,
                          interpret: bool = False):
    """:func:`kda_mixed`'s value by the Pallas kernel: ``y [B, S,
    H·w]``, float32 ``taps [K, H·w]`` → ``[B, S, H, w]`` in ``y``'s
    type, written in chunks of ``chunk`` rows, chunk-major."""
    b, s_len, width = y.shape
    head, halo = width // heads, KDA_MIX_HALO
    grid, taps_spec, y_rows, chunk_rows = _mix_specs(
        y, taps, head, rows, lanes, chunk)
    out = _mix_call(
        functools.partial(_mix_forward_kernel, norm=norm),
        y, "kda_mixed_fwd", 2, 24, grid=grid, interpret=interpret,
        in_specs=[y_rows(halo, -1), y_rows(rows), taps_spec],
        out_specs=chunk_rows(rows),
        out_shape=jax.ShapeDtypeStruct(
            (s_len // chunk, b, heads, chunk, head), y.dtype)
    )(y, y, taps)
    return out.transpose(1, 0, 3, 2, 4).reshape(b, s_len, heads, head)


def mixing_kernel_backward(y, taps, d, heads: int, norm, *,
                           rows: int = KDA_MIX_ROWS,
                           lanes: int = KDA_MIX_LANES,
                            chunk: int = KDA_CHUNK,
                           interpret: bool = False):
    """:func:`kda_mixed`'s backward by the Pallas kernel: ``y``, the
    taps and the cotangent ``d [B, S, H, w]`` (read chunk-major) →
    ``dy`` in ``y``'s type and float32 ``dtaps``."""
    from jax.experimental import pallas as pl

    b, _, width = y.shape
    head, halo, k = width // heads, KDA_MIX_HALO, taps.shape[0]
    grid, taps_spec, y_rows, chunk_rows = _mix_specs(
        y, taps, head, rows, lanes, chunk)
    d = _chunk_major(d, chunk)
    dy, dtaps = _mix_call(
        functools.partial(_mix_backward_kernel, norm=norm),
        y, "kda_mixed_bwd", 3, 64, grid=grid, interpret=interpret,
        in_specs=[y_rows(halo, -1), y_rows(rows), y_rows(halo, 1),
                  chunk_rows(rows), chunk_rows(halo, 1), taps_spec],
        out_specs=[y_rows(rows),
                   pl.BlockSpec((1, 1, k, lanes),
                                lambda b, i, j: (b, i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct((b, grid[1], k, width),
                                        jnp.float32)]
    )(y, y, y, d, d, taps)
    return dy, dtaps.sum((0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def kda_mixed(y, taps, heads: int, norm):
    """A projection's product ``y [B, S, H·w]`` → the scan's operand
    ``[B, S, H, w]`` in ``y``'s type: :func:`causal_conv` with float32
    ``taps [K, H·w]``, SiLU, and where ``norm`` is a number
    :func:`l2_normed` over a head divided by it — all in float32. The
    backward keeps ``y`` and the taps alone (comment above)."""
    if use_mixing_kernel(y, taps, heads):
        return mixing_kernel_forward(y, taps, heads, norm)
    return _mixed_by_definition(y, taps, heads, norm)


def _kda_mixed_fwd(y, taps, heads, norm):
    return kda_mixed(y, taps, heads, norm), (y, taps)


def _kda_mixed_bwd(heads, norm, kept, d):
    y, taps = kept
    if use_mixing_kernel(y, taps, heads):
        return mixing_kernel_backward(y, taps, d, heads, norm)
    return jax.vjp(lambda y, taps: _mixed_by_definition(
        y, taps, heads, norm), y, taps)[1](d)


kda_mixed.defvjp(_kda_mixed_fwd, _kda_mixed_bwd)


# -------------------------------------------------------------- modules

def _weight(module, name: str, shape: tuple, dtype):
    """A float32 parameter, in the compute type."""
    return module.param(name, nn.initializers.normal(0.02), shape,
                        jnp.float32).astype(dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones,
                           (x.shape[-1],), jnp.float32)
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return xf * jax.lax.rsqrt(var + self.eps) * scale


def _gated(g, u):
    """The inside of SwiGLU, in float32."""
    return (jax.nn.silu(g.astype(jnp.float32))
            * u.astype(jnp.float32)).astype(g.dtype)


def _swiglu(x, w_gate, w_up, w_down):
    return jnp.dot(_gated(jnp.dot(x, w_gate), jnp.dot(x, w_up)), w_down)


class SwiGLU(nn.Module):
    width: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        d = x.shape[-1]
        return _swiglu(
            x, _weight(self, "gate_proj", (d, self.width), self.dtype),
            _weight(self, "up_proj", (d, self.width), self.dtype),
            _weight(self, "down_proj", (self.width, d), self.dtype))


class GatedAttention(nn.Module):
    spec: LayerSpec
    kv_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        spec, hd = self.spec, self.head_dim
        b, s_len, d = x.shape
        h, g = spec.heads, self.kv_heads
        with jax.named_scope(scopes.SEQ_ATTN_PROJ):
            q = jnp.dot(x, _weight(self, "q_proj", (d, h * hd),
                                   self.dtype))
            k = jnp.dot(x, _weight(self, "k_proj", (d, g * hd),
                                   self.dtype))
            v = jnp.dot(x, _weight(self, "v_proj", (d, g * hd),
                                   self.dtype))
        with jax.named_scope(scopes.SEQ_ATTN_GATE):
            gate = jax.nn.sigmoid(jnp.dot(
                x, _weight(self, "gate_proj", (d, h), self.dtype)
            ).astype(jnp.float32))
        with jax.named_scope(scopes.SEQ_ATTN_ROPE):
            q = apply_rope(q.reshape(b, s_len, h, hd), spec.rope,
                           1.0 / math.sqrt(hd))
            k = apply_rope(k.reshape(b, s_len, g, hd), spec.rope)
        v = v.reshape(b, s_len, g, hd)
        a = _attention(q, k, v, spec.window)
        with jax.named_scope(scopes.SEQ_ATTN_GATE):
            a = (a * gate[..., None]).astype(self.dtype)
        with jax.named_scope(scopes.SEQ_ATTN_OUT):
            return jnp.dot(
                a.reshape(b, s_len, h * hd),
                _weight(self, "o_proj", (h * hd, d), self.dtype))


class LatentAttention(nn.Module):
    """Multi-head latent attention as it trains: queries and
    keys/values through low-rank paths with a norm each, per head a
    non-rotary part and a rotary part whose key all heads share, so
    many heads with a key and a value each (``spec.latent``)."""

    spec: LayerSpec
    eps: float
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        spec, lat = self.spec, self.spec.latent
        b, s_len, d = x.shape
        h, qk = spec.heads, lat.nope + lat.rope
        with jax.named_scope(scopes.SEQ_ATTN_PROJ):
            if lat.q_rank:
                c_q = RMSNorm(self.eps, name="q_a_norm")(jnp.dot(
                    x, _weight(self, "q_a_proj", (d, lat.q_rank),
                               self.dtype)))
                q = jnp.dot(c_q.astype(self.dtype), _weight(
                    self, "q_b_proj", (lat.q_rank, h * qk), self.dtype))
            else:
                q = jnp.dot(x, _weight(self, "q_proj", (d, h * qk),
                                       self.dtype))
            q = q.reshape(b, s_len, h, qk)
            kv_a = jnp.dot(x, _weight(
                self, "kv_a_proj", (d, lat.kv_rank + lat.rope),
                self.dtype))
            c_kv = RMSNorm(self.eps, name="kv_a_norm")(
                kv_a[..., :lat.kv_rank])
            kv = jnp.dot(
                c_kv.astype(self.dtype),
                _weight(self, "kv_b_proj",
                        (lat.kv_rank, h * (lat.nope + lat.value)),
                        self.dtype)
            ).reshape(b, s_len, h, lat.nope + lat.value)
        with jax.named_scope(scopes.SEQ_ATTN_ROPE):
            # the scores' scale goes on the queries, in float32
            q_nope = (q[..., :lat.nope].astype(jnp.float32)
                      * lat.scale).astype(self.dtype)
            q_pe = apply_rope(q[..., lat.nope:], spec.rope, lat.scale)
            k_pe = apply_rope(kv_a[:, :, None, lat.kv_rank:], spec.rope)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            k = jnp.concatenate(
                [kv[..., :lat.nope],
                 jnp.broadcast_to(k_pe, (b, s_len, h, lat.rope))],
                axis=-1)
        a = _attention(q, k, kv[..., lat.nope:], spec.window)
        if lat.gate:
            with jax.named_scope(scopes.SEQ_ATTN_GATE):
                gate = jax.nn.sigmoid(jnp.dot(
                    x, _weight(self, "gate_proj", (d, h), self.dtype)
                ).astype(jnp.float32))
                a = a * gate[..., None]
        with jax.named_scope(scopes.SEQ_ATTN_OUT):
            return jnp.dot(
                a.astype(self.dtype).reshape(b, s_len, h * lat.value),
                _weight(self, "o_proj", (h * lat.value, d), self.dtype))


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log U(1, 16)`` (``fla/layers/kda.py``)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step drawn log-uniformly from
    ``[0.001, 0.1]`` (the same file; Mamba's)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype,
                                    math.log(0.001), math.log(0.1)))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


class KimiDeltaAttention(nn.Module):
    """Kimi delta attention (``spec.kda``): queries, keys and values
    through a projection and then, as ONE pass from the projection's
    product to the scan's operand (:func:`kda_mixed`: a Pallas kernel
    on a TPU, XLA elsewhere, one hand-written backward), a depthwise
    causal convolution and SiLU each, queries and keys L2-normed per
    head and the queries divided by ``√d_k``; a log-decay per key
    channel held above the config's lower bound; a delta-rule
    recurrence per head (:func:`kda_chunked`); the result normed per
    head, gated channel by channel and projected back. No rotary."""

    spec: LayerSpec
    eps: float
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        spec, kda = self.spec, self.spec.kda
        b, s_len, d = x.shape
        h, dk, dv = spec.heads, kda.key, kda.value
        f32 = jnp.float32

        def mixed(name: str, width: int, norm=None):
            """Projection, then convolution, SiLU and where ``norm``
            is given the L2 norm over a head divided by it, as one
            pass (:func:`kda_mixed`): ``[B, S, H, width]`` in the
            compute type. A pass that ends in a norm runs under the
            norm's scope, the values' under the convolution's."""
            y = jnp.dot(x, _weight(self, f"{name}_proj", (d, h * width),
                                   self.dtype))
            taps = self.param(f"{name}_conv",
                              nn.initializers.normal(0.02),
                              (kda.conv, h * width), f32)
            if norm is None:
                with jax.named_scope(scopes.SEQ_ATTN_KDA_CONV):
                    return kda_mixed(y, taps, h, norm)
            with jax.named_scope(scopes.SEQ_ATTN_KDA_QKNORM):
                return kda_mixed(y, taps, h, norm)

        with jax.named_scope(scopes.SEQ_ATTN_KDA_PROJ):
            q = mixed("q", dk, math.sqrt(dk))
            k = mixed("k", dk, 1.0)
            v = mixed("v", dv)
            with jax.named_scope(scopes.SEQ_ATTN_KDA_DECAY):
                beta = jax.nn.sigmoid(jnp.dot(
                    x, _weight(self, "b_proj", (d, h), self.dtype),
                    preferred_element_type=f32))
                # the log-decay: lower · σ(e^{A_log} (x W_f +
                # dt_bias)), in (lower, 0) whatever the weights
                rate = jnp.exp(self.param("A_log", _a_log_init, (h,),
                                          f32))
                raw = jnp.dot(
                    x, _weight(self, "f_proj", (d, h * dk), self.dtype),
                    preferred_element_type=f32
                ) + self.param("dt_bias", _dt_bias_init, (h * dk,), f32)
                g = kda.lower * jax.nn.sigmoid(
                    rate[:, None] * raw.reshape(b, s_len, h, dk))
        with jax.named_scope(scopes.SEQ_ATTN_KDA_SCAN):
            o = kda_chunked(q, k, v, g, beta, self.dtype)
        with jax.named_scope(scopes.SEQ_ATTN_KDA_OUT):
            gate = jax.nn.sigmoid(jnp.dot(
                x, _weight(self, "g_proj", (d, h * dv), self.dtype)
            ).astype(f32))
            o = RMSNorm(self.eps, name="o_norm")(o).reshape(
                b, s_len, h * dv) * gate
            return jnp.dot(
                o.astype(self.dtype),
                _weight(self, "o_proj", (h * dv, d), self.dtype))


# ----------------------------------------------------- hyper-connections
#
# The residual state is ``n`` streams side by side, ``[B, S, n·D]``
# (stream ``i`` the columns ``i·D .. (i+1)·D``: whole lane tiles, and
# ``vec(X)`` as it lies). A sublayer F becomes
#
#     u = H_pre X;  y = F(RMSNorm(u));  X <- H_res X + H_post^T y
#
# with per-token coefficients ``H_pre, H_post [n]`` and ``H_res
# [n, n]``. The coefficients live with the TOKENS ON THE LANE AXIS
# (``[n, B·S]``, ``[n, n, B·S]``): a ``[T, 4, 4]`` array would pad
# every token's sixteen numbers to a tile.

def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """``exp(m)`` ``[n, n, …]`` made doubly stochastic: ``iters``
    times its rows (sums over axis 1), then its columns (over axis
    0), each divided by its sum plus ``eps``. Differentiated as
    written, through every iteration (a ``scan``: one body in the
    program and one in its transpose, not ``iters`` copies of each
    in every sublayer's forward, recomputation and backward)."""
    def once(m, _):
        m = m / (m.sum(axis=1, keepdims=True) + eps)
        return m / (m.sum(axis=0, keepdims=True) + eps), None

    return jax.lax.scan(once, jnp.exp(m), None, length=iters)[0]


def _streams(x: jax.Array, n: int) -> list:
    d = x.shape[-1] // n
    return [x[..., i * d:(i + 1) * d].astype(jnp.float32)
            for i in range(n)]


def _per_token(c: jax.Array, like: jax.Array) -> jax.Array:
    """A coefficient ``[B·S]`` as a column beside ``like [B, S, D]``."""
    return c.reshape(like.shape[:-1] + (1,))


def mix_in(x: jax.Array, pre: jax.Array) -> jax.Array:
    """``H_pre X``: the streams ``x [B, S, n·D]`` mixed into one
    float32 ``[B, S, D]`` under ``pre [n, B·S]``."""
    parts = _streams(x, pre.shape[0])
    return sum(_per_token(pre[i], p) * p for i, p in enumerate(parts))


def mix_out(x: jax.Array, y: jax.Array, res: jax.Array,
            post: jax.Array) -> jax.Array:
    """``H_res X + H_post^T y``: ``x [B, S, n·D]``, the sublayer's
    ``y [B, S, D]``, ``res [n, n, B·S]``, ``post [n, B·S]`` → the
    streams, in ``x``'s type (the sums in float32)."""
    n = post.shape[0]
    parts, yf = _streams(x, n), y.astype(jnp.float32)
    return jnp.concatenate([
        (sum(_per_token(res[i, j], p) * p for j, p in enumerate(parts))
         + _per_token(post[i], yf) * yf).astype(x.dtype)
        for i in range(n)], axis=-1)


def _logit(p: float) -> float:
    return math.log(p / (1.0 - p))


#: ``b_res`` off the diagonal at the start (0 on it): ``H_res`` starts
#: near the identity, 0.95 on the diagonal
HYPER_RES_OFF_DIAGONAL = -4.0


class HyperConnection(nn.Module):
    """One sublayer's coefficients from the streams ``[B, S, n·D]``:
    ``pre [n, T]``, ``post [n, T]`` and ``res [n, n, T]`` (``T =
    B·S``), all float32. ``x' = RMSNorm(vec(X))`` over all ``n·D``
    with a learned scale; ``H~ = alpha · (x' phi) + b`` for each of
    the three; ``pre = sigmoid``, ``post = 2 sigmoid``, ``res =
    Sinkhorn(clip(·))``.

    The three products are one: the norm's scale is folded into the
    ``phi``s' rows and its statistic multiplies the ``[T, 2n + n²]``
    result, so the normed copy of the streams is never laid out."""

    hyper: Hyper
    eps: float

    @nn.compact
    def __call__(self, x: jax.Array):
        hyper, n = self.hyper, self.hyper.streams
        nd = x.shape[-1]
        normal = nn.initializers.normal(0.02)
        with jax.named_scope(scopes.SEQ_MHC_COEFF):
            scale = self.param("norm", nn.initializers.ones, (nd,),
                               jnp.float32)
            phi = jnp.concatenate([
                self.param("phi_pre", normal, (nd, n), jnp.float32),
                self.param("phi_post", normal, (nd, n), jnp.float32),
                self.param("phi_res", normal, (nd, n * n), jnp.float32),
            ], axis=1) * scale[:, None]
            alpha = [self.param(f"alpha_{k}", nn.initializers.constant(
                0.01), (), jnp.float32) for k in ("pre", "post", "res")]
            b_pre = self.param("b_pre", nn.initializers.constant(
                _logit(1.0 / n) if n > 1 else 0.0), (n,), jnp.float32)
            b_post = self.param("b_post", nn.initializers.zeros, (n,),
                                jnp.float32)
            b_res = self.param(
                "b_res", lambda *_: HYPER_RES_OFF_DIAGONAL
                * (1.0 - jnp.eye(n, dtype=jnp.float32)))
            flat = x.reshape(-1, nd).astype(jnp.float32)
            var = jnp.mean(flat * flat, axis=-1)
            raw = (jnp.dot(flat, phi,
                           precision=jax.lax.Precision.HIGHEST)
                   * jax.lax.rsqrt(var + self.eps)[:, None]).T
            pre = jax.nn.sigmoid(alpha[0] * raw[:n] + b_pre[:, None])
            post = 2.0 * jax.nn.sigmoid(
                alpha[1] * raw[n:2 * n] + b_post[:, None])
            res = (alpha[2] * raw[2 * n:].reshape(n, n, -1)
                   + b_res[:, :, None])
        with jax.named_scope(scopes.SEQ_MHC_SINKHORN):
            res = sinkhorn(jnp.clip(res, *hyper.clamp), hyper.iters,
                           hyper.eps)
        return pre, post, res


#: tokens the expert layer takes at a time: its buffers are this many
#: times ``top_k`` rows long (fewer tokens are one chunk)
EXPERT_CHUNK = 4096
#: rows of the expert buffer that its dense passes take at a time
#: (a buffer this does not divide, or a shorter one, is one block).
#: Read off the v5e: 512 and 1,024 alike, 2,048 and 4,096 a little
#: slower on 2,540 pairs a chunk (PERF.md, PR 27)
EXPERT_ROW_BLOCK = 1024


def _row_block(rows: int) -> int:
    """Height of the row blocks of a buffer of ``rows`` rows."""
    return rows if rows % EXPERT_ROW_BLOCK else EXPERT_ROW_BLOCK


def _held_blocks(n_held, rows: int, bufs: tuple, block_fn):
    """``bufs`` (zeros each) with what the row blocks that hold one
    of the leading ``n_held`` of a buffer's ``rows`` rows — the rows
    with a pair — give them. ``block_fn(at)``, where ``at(a)`` is the
    block's rows of an ``a [R, …]``, returns one entry per buffer:
    the block's rows of a buffer ``[R, …]``, written in their place;
    or ``(index, values)`` for a buffer that is not row-major (a
    token's sum ``[T, D]``, a pair's scalar ``[T·K]``): the block's
    ``values`` ADDED at its ``index [block]``, which may repeat — a
    token holding several pairs of one block. A row past ``n_held``
    writes and adds zero whatever ``block_fn`` gives it (a select: a
    product's leavings do not spread).

    The loop is as long as the blocks that hold a pair, so a block
    past them costs its share of the zero fill and nothing else. It
    is never differentiated: every caller is a ``custom_vjp`` rule.
    What the body reads should be there already: XLA sinks a
    producer that only the loop uses into its body, once a turn, and
    copies a product's output before a loop may write to it (PERF.md,
    PR 27) — so every buffer is a fresh zero array."""
    block = _row_block(rows)

    def body(i, bufs):
        start = i * block
        live = start + jnp.arange(block) < n_held

        def put(buf, new):
            index, new = new if isinstance(new, tuple) else (None, new)
            new = jnp.where(live.reshape((-1,) + (1,) * (new.ndim - 1)),
                            new, 0).astype(buf.dtype)
            if index is None:
                return jax.lax.dynamic_update_slice_in_dim(
                    buf, new, start, 0)
            return buf.at[index].add(new)

        return tuple(map(put, bufs, block_fn(
            lambda a: jax.lax.dynamic_slice_in_dim(a, start, block, 0))))

    return jax.lax.fori_loop(0, -(-n_held // block), body, bufs)


@jax.custom_vjp
def _dispatch(x, tok, n_held):
    """Rows of ``x [T, D]`` in pair order, once for each of the two
    products that read them (one buffer): row ``i`` is token
    ``tok[i]``, the token of the sorted pair the buffer's row ``i``
    holds, zero past the ``n_held`` rows that hold a pair. Per block:
    a gather of the block's tokens."""
    xs, = _held_blocks(
        n_held, tok.shape[0],
        (jnp.zeros((tok.shape[0], x.shape[1]), x.dtype),),
        lambda at: (x[at(tok)],))
    return xs, xs


def _dispatch_fwd(x, tok, n_held):
    # ``x`` for its shape and type alone: nothing of it is kept
    return _dispatch(x, tok, n_held), (x, tok, n_held)


def _dispatch_bwd(res, g):
    """Per block: the two products' cotangents summed in float32 and
    added to their tokens' rows of a float32 ``[T, D]``; no pass is
    longer than the rows that hold a pair."""
    x, tok, n_held = res
    back, = _held_blocks(
        n_held, tok.shape[0], (jnp.zeros(x.shape, jnp.float32),),
        lambda at: ((at(tok), at(g[0]).astype(jnp.float32)
                     + at(g[1]).astype(jnp.float32)),))
    return back.astype(x.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _act(g, u, n_held):
    """``silu(g) * u`` on the ``n_held`` rows of the buffer that hold
    a pair, zero on the others whatever the products left there;
    ``g, u [R, F]``."""
    return _held_blocks(n_held, g.shape[0], (jnp.zeros_like(g),),
                        lambda at: (_gated(at(g), at(u)),))[0]


def _act_fwd(g, u, n_held):
    return _act(g, u, n_held), (g, u, n_held)


def _act_bwd(res, dh):
    g, u, n_held = res
    return *_held_blocks(
        n_held, g.shape[0], (jnp.zeros_like(g), jnp.zeros_like(u)),
        lambda at: jax.vjp(_gated, at(g), at(u))[1](at(dh))), None


_act.defvjp(_act_fwd, _act_bwd)


@jax.custom_vjp
def _combine(y, weight, pairs, tok, n_held):
    """The pairs' results ``y [R, D]`` summed by token under their
    float32 ``weight [T, K]``: ``[T, D]`` float32. Row ``i`` of the
    buffer holds pair ``pairs[i]`` of token ``tok[i]``; a pair that
    was not sent here, or that the buffer left out, adds nothing.
    Per block: the block's rows times their pairs' weights, added to
    their tokens' rows of the sum."""
    flat = weight.reshape(-1)
    out, = _held_blocks(
        n_held, y.shape[0],
        (jnp.zeros((weight.shape[0], y.shape[1]), jnp.float32),),
        lambda at: ((at(tok), at(y).astype(jnp.float32)
                     * flat[at(pairs)][:, None]),))
    return out


def _combine_fwd(y, weight, pairs, tok, n_held):
    return (_combine(y, weight, pairs, tok, n_held),
            (y, weight, pairs, tok, n_held))


def _combine_bwd(res, d):
    """Per block, from one gather of the block's tokens' rows of ``d
    [T, D]``: a row's cotangent is its token's times the pair's
    weight, and a pair's weight's is its row's dot with its token's —
    a scalar placed at the pair. Nothing is laid out ``[T, K, D]``."""
    y, weight, pairs, tok, n_held = res
    flat = weight.reshape(-1)

    def block_bwd(at):
        at_pairs, rows = at(pairs), d[at(tok)]
        return (rows * flat[at_pairs][:, None],
                (at_pairs, (rows * at(y).astype(jnp.float32)).sum(-1)))

    dy, dw = _held_blocks(
        n_held, y.shape[0],
        (jnp.zeros_like(y), jnp.zeros_like(flat)), block_bwd)
    return dy, dw.reshape(weight.shape), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def held_experts(x, local, weight, w_gate, w_up, w_down,
                 rows: int | None = None):
    """What the held experts add for one chunk of tokens.

    ``x [T, D]``; ``local [T, K]`` the index of each chosen expert
    among the ``E`` held ones (anything outside ``0..E-1``: not held
    here); ``weight [T, K]`` float32; the held experts' matrices
    ``[E, D, F]``, ``[E, D, F]``, ``[E, F, D]``. Returns the weighted
    sum ``[T, D]`` float32, the pairs per held expert ``[E]``, the
    pairs sent here that the buffer left out, and the buffer's row
    blocks ``[2]``: those that held a pair, and all.

    The pairs sent here are sorted to the front of a buffer of
    ``rows`` rows — all ``T·K`` unless a caller says otherwise, so
    that every pair fits however the router sends them. The count of
    pairs left out is what arrived less what the products were given:
    a buffer cut below what arrives shows in it.

    Each grouped product is one ``ragged_dot`` over the whole buffer
    and skips the rows past its groups. Everything else — tokens'
    rows into the buffer, SwiGLU's inside, the results summed by
    token, and each one's backward rule — runs in blocks of
    :data:`EXPERT_ROW_BLOCK` rows of the buffer and only on the
    blocks that hold a pair (:func:`_held_blocks`), so the work
    follows the pairs that arrived — all of the blocks when every
    pair lands here, three of forty on balanced traffic — and
    neither the buffer's length nor the ``T·K`` pairs of the chunk."""
    t, k = local.shape
    rows = t * k if rows is None else rows
    e = w_gate.shape[0]
    with jax.named_scope(scopes.SEQ_EXPERTS_SORT):
        held = (local >= 0) & (local < e)
        key = jnp.where(held, local, e).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        sizes = (key[:, None] == jnp.arange(e)[None, :]).sum(
            axis=0, dtype=jnp.int32)
        # the buffer's rows: the pair each holds, and its token
        mine = order[:rows]
        tok = mine // k
        # each expert's pairs that lie inside the buffer
        ends = jnp.minimum(jnp.cumsum(sizes), rows)
        computed = jnp.diff(ends, prepend=0)
        n_held = ends[-1]
        block = _row_block(rows)
        blocks = jnp.stack([-(-n_held // block),
                            jnp.int32(rows // block)])

    def product(a, w):
        # rows past the held pairs belong to no group: the product
        # skips them, and what it leaves there is never read
        return jax.lax.ragged_dot(a, w, computed)

    # a rule's backward pass runs under the scope of its call
    with jax.named_scope(scopes.SEQ_EXPERTS_DISPATCH):
        xs_gate, xs_up = _dispatch(x, tok, n_held)
    g, u = product(xs_gate, w_gate), product(xs_up, w_up)
    with jax.named_scope(scopes.SEQ_EXPERTS_ACT):
        h = _act(g, u, n_held)
    y = product(h, w_down)
    with jax.named_scope(scopes.SEQ_EXPERTS_COMBINE):
        out = _combine(y, weight, mine, tok, n_held)
    dropped = sizes.sum() - computed.sum()
    return out, sizes, dropped, blocks


#: spread of the seeded selection bias: two steps of the published
#: update rule (±0.001 a step, DeepSeek-V3 report §4.2). Non-zero, so
#: that selection and weighting can be told apart; small, because the
#: top 4 of 64 is a tail: a spread of 0.02 alone swings the load of
#: eight held experts by 4.6 % (std) with the seed (PERF.md, PR 30)
ROUTER_BIAS_STD = 0.002
#: the published rule's step (the same report; the config has no key
#: for it)
ROUTER_BIAS_RATE = 0.001


def bias_step(load: jax.Array) -> jax.Array:
    """The selection bias's move after a step, by the published rule:
    up by ``ROUTER_BIAS_RATE`` for an expert that took fewer of the
    step's tokens than the mean, down for one that took more. ``load
    [E]`` counts the choices of the tokens HERE over ALL experts (the
    router is whole on every chip); no gradient is involved."""
    load = load.astype(jnp.float32)
    return ROUTER_BIAS_RATE * jnp.sign(load.mean() - load)


def group_limited(scores: jax.Array, n_group: int, topk_group: int
                  ) -> jax.Array:
    """``scores [T, E]`` with every expert outside the ``topk_group``
    best of ``n_group`` groups at −∞ (DeepSeek-V3's group-limited
    choice: the experts lie in groups by index — a group to a node
    of the deployment — and a group scores the sum of its two
    best). One group: the scores as they are."""
    if n_group == 1:
        return scores
    t, e = scores.shape
    grouped = scores.reshape(t, n_group, e // n_group)
    best_two = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)
    _, kept = jax.lax.top_k(best_two, topk_group)
    stays = (kept[..., None] == jnp.arange(n_group)).any(axis=1)
    return jnp.where(stays[..., None], grouped, -jnp.inf).reshape(t, e)


class SparseFFN(nn.Module):
    """Router over all ``num_experts``, the held experts' part of the
    routed result, and the shared expert.

    ``scoring`` ``softmax``: the top ``top_k`` of the softmax, by and
    with its probabilities. ``sigmoid``: scores ``s = sigmoid(x W_r)``;
    the choice is the top ``top_k`` of ``s + b`` and the weights are
    the chosen ``s`` — the bias steers the choice alone and carries
    no gradient. ``b`` (``router_bias``) is a leaf of the parameter
    tree under a ``stop_gradient``, not a collection of its own: the
    model JSON, the checkpoints and the trainer's state carry one
    tree, and the optimizer's update of a zero gradient leaves the
    leaf as it is. With ``n_group`` groups the choice is among the
    ``topk_group`` best groups' experts (:func:`group_limited`).
    What moves the bias is the published rule
    (:func:`bias_step`): the layer returns the move with its counts
    (``bias_step``) and the train step adds it after the optimizer's
    update — without it the held experts' load drifts with the
    router's first steps, and a step's time with the load."""

    num_experts: int
    top_k: int
    width: int
    shared_width: int
    experts_held: int
    expert_offset: int
    norm_topk: bool
    routed_scale: float
    scoring: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array):
        """``x`` is the normed input in float32; returns the layer's
        output and its routing counts."""
        b, s_len, d = x.shape
        t = b * s_len
        flat = x.reshape(t, d)
        with jax.named_scope(scopes.SEQ_ROUTER):
            w_r = self.param("router", nn.initializers.normal(0.02),
                             (d, self.num_experts), jnp.float32)
            logits = jnp.dot(flat, w_r,
                             precision=jax.lax.Precision.HIGHEST)
            if self.scoring == "sigmoid":
                bias = self.param("router_bias",
                                  nn.initializers.normal(ROUTER_BIAS_STD),
                                  (self.num_experts,), jnp.float32)
                p = jax.nn.sigmoid(logits)
                _, chosen = jax.lax.top_k(group_limited(
                    p + jax.lax.stop_gradient(bias), self.n_group,
                    self.topk_group), self.top_k)
                weight = jnp.take_along_axis(p, chosen, axis=-1)
                moves = {"bias_step": bias_step((
                    chosen[..., None] == jnp.arange(self.num_experts)
                ).sum(axis=(0, 1)))}
            else:
                p = jax.nn.softmax(logits)
                weight, chosen = jax.lax.top_k(p, self.top_k)
                moves = {}
            if self.norm_topk:
                weight = weight / weight.sum(axis=-1, keepdims=True)
            local = chosen - self.expert_offset
            # for a comparison of choices (``chipbench``): kept only
            # by a caller that makes ``intermediates`` mutable
            self.sow("intermediates", "chosen", chosen)
        xb = flat.astype(self.dtype)
        with jax.named_scope(scopes.SEQ_EXPERTS):
            e, f = self.experts_held, self.width
            mats = (_weight(self, "experts_gate", (e, d, f), self.dtype),
                    _weight(self, "experts_up", (e, d, f), self.dtype),
                    _weight(self, "experts_down", (e, f, d), self.dtype))
            chunk = min(EXPERT_CHUNK, t)
            if t % chunk:
                raise ValueError(
                    f"{t} tokens are not whole chunks of {chunk}")
            n = t // chunk
            routed, sizes, dropped, blocks = jax.lax.map(
                jax.checkpoint(lambda a: held_experts(*a, *mats)),
                (xb.reshape(n, chunk, d),
                 local.reshape(n, chunk, self.top_k),
                 weight.reshape(n, chunk, self.top_k)))
            routed = routed.reshape(t, d) * self.routed_scale
            sizes = sizes.sum(axis=0)
            blocks = blocks.sum(axis=0)
        with jax.named_scope(scopes.SEQ_SHARED):
            shared = SwiGLU(self.shared_width, self.dtype,
                            name="shared")(xb)
        out = (routed + shared.astype(jnp.float32)).astype(self.dtype)
        stats = {"moe_routed": jnp.int32(t * self.top_k),
                 "moe_held": sizes.sum(),
                 "moe_dropped": dropped.sum(),
                 "moe_load_max": sizes.max(),
                 "moe_row_blocks_run": blocks[0],
                 "moe_row_blocks": blocks[1], **moves}
        return out.reshape(b, s_len, d), stats


class DecoderLayer(nn.Module):
    """An attention sublayer and an FFN sublayer, each ``x + F(norm
    (x))`` — or, with ``hyper``, each a hyper-connected sublayer over
    the streams ``[B, S, n·D]`` with coefficients of its own."""

    spec: LayerSpec
    kv_heads: int
    head_dim: int
    dense_width: int
    ffn: tuple              # SparseFFN's fields, as sorted items
    eps: float
    dtype: jnp.dtype = jnp.bfloat16
    hyper: Hyper | None = None

    def _mixed(self, name: str, x: jax.Array, f):
        """A hyper-connected sublayer: ``f`` (its input → its output
        and counts) on the streams' mix, written back to them."""
        pre, post, res = HyperConnection(self.hyper, self.eps,
                                         name=name)(x)
        with jax.named_scope(scopes.SEQ_MHC_MIX):
            u = mix_in(x, pre)
        y, stats = f(u)
        with jax.named_scope(scopes.SEQ_MHC_MIX):
            return mix_out(x, y, res, post), stats

    @nn.compact
    def __call__(self, x: jax.Array):
        spec = self.spec

        def attn_scope():
            return (jax.named_scope(scopes.SEQ_ATTN_KDA) if spec.kda
                    else jax.named_scope(scopes.SEQ_ATTN_MLA)
                    if spec.latent
                    else jax.named_scope(scopes.SEQ_ATTN_WINDOW)
                    if spec.window
                    else jax.named_scope(scopes.SEQ_ATTN_FULL))

        def attention(x):
            n = RMSNorm(self.eps, name="input_norm")(x).astype(
                self.dtype)
            if spec.kda:
                return KimiDeltaAttention(spec, self.eps, self.dtype,
                                          name="attn")(n)
            if spec.latent:
                return LatentAttention(spec, self.eps, self.dtype,
                                       name="attn")(n)
            return GatedAttention(spec, self.kv_heads, self.head_dim,
                                  self.dtype, name="attn")(n)

        def ffn(h):
            norm = RMSNorm(self.eps, name="post_attn_norm")
            if spec.sparse:
                with jax.named_scope(scopes.SEQ_ROUTER):
                    n = norm(h)
                return SparseFFN(dtype=self.dtype, name="ffn",
                                 **dict(self.ffn))(n)
            with jax.named_scope(scopes.SEQ_DENSE_FFN):
                return SwiGLU(self.dense_width, self.dtype, name="ffn")(
                    norm(h).astype(self.dtype)), None

        if self.hyper is None:
            with attn_scope():
                h = x + attention(x)
            f, stats = ffn(h)
            return h + f, stats

        def scoped_attention(u):
            with attn_scope():
                return attention(u), None

        h, _ = self._mixed("attn_hc", x, scoped_attention)
        return self._mixed("ffn_hc", h, ffn)


class SeqPolicyNet(nn.Module):
    """ids ``[B, S]`` → (float32 logits ``[B, S, vocab_held]``, the
    step's routing counts). Beside the counts, where a router has a
    selection bias: ``no_grad_updates``, the biases' moves by the
    published rule as a part of the parameter tree, for the train
    step to add. With a multi-token-prediction module
    (``mtp``) and the next ids ``[B, S]`` given, the counts come with
    ``mtp_logits`` ``[B, S, vocab_held]``: at each position the
    logits of the id AFTER the next — and, where the config weighs
    the module's loss itself, ``mtp_loss_weight``."""

    layers: tuple           # of LayerSpec
    hidden: int
    vocab_held: int
    kv_heads: int
    head_dim: int
    dense_width: int
    ffn: tuple              # SparseFFN's fields, as sorted items
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16
    hyper: Hyper | None = None
    mtp: int = 0            # multi-token-prediction modules: 0 or 1
    #: the module's block, where it is not the last layer's kind
    mtp_layer: LayerSpec | None = None
    #: the module's loss weight, where the config has a key for it
    #: (else the trainer's own)
    mtp_weight: float | None = None

    @nn.compact
    def __call__(self, ids: jax.Array, next_ids: jax.Array | None = None):
        with jax.named_scope(scopes.SEQ_EMBED):
            table = self.param("embed", nn.initializers.normal(0.02),
                               (self.vocab_held, self.hidden),
                               jnp.float32)
            x = jnp.take(table, ids, axis=0).astype(self.dtype)
        totals = dict.fromkeys(MOE_STATS, jnp.int32(0))
        moves = {}
        layer = nn.remat(
            DecoderLayer,
            policy=jax.checkpoint_policies.save_only_these_names(
                KERNEL_RESIDUALS))

        def block(spec, name, x):
            """One layer on the state, its counts merged."""
            x, stats = layer(
                spec, self.kv_heads, self.head_dim, self.dense_width,
                self.ffn, self.eps, self.dtype, self.hyper,
                name=name)(x)
            if stats is not None:
                for key in MOE_STATS:
                    merge = (jnp.maximum if key == "moe_load_max"
                             else jnp.add)
                    totals[key] = merge(totals[key], stats[key])
                if "bias_step" in stats:
                    moves[name] = {"ffn": {
                        "router_bias": stats["bias_step"]}}
            return x

        def extras(**more):
            if moves:
                more["no_grad_updates"] = {"params": moves}
            return dict(totals, **more)

        def enter(x):
            """The streams start as copies of ``x``."""
            if not self.hyper:
                return x
            return jnp.tile(x, (1, 1, self.hyper.streams))

        def leave(x):
            """And end as their sum."""
            if not self.hyper:
                return x
            return sum(_streams(x, self.hyper.streams)).astype(
                self.dtype)

        x = enter(x)
        for i, spec in enumerate(self.layers):
            x = block(spec, f"layer{i}", x)
        with jax.named_scope(scopes.SEQ_MHC_MIX):
            x = leave(x)
        with jax.named_scope(scopes.SEQ_HEAD):
            n = RMSNorm(self.eps, name="norm")(x).astype(self.dtype)
            head_w = _weight(self, "head", (self.hidden, self.vocab_held),
                             self.dtype)
            logits = jnp.dot(n, head_w,
                             preferred_element_type=jnp.float32)
        if not self.mtp or next_ids is None:
            return logits, extras()
        # the module's own parts under its scope; its block under the
        # layers' (it is the last layer's kind)
        with jax.named_scope(scopes.SEQ_MTP):
            ahead = jnp.take(table, next_ids, axis=0).astype(self.dtype)
            joined = jnp.concatenate([
                RMSNorm(self.eps, name="mtp_hnorm")(x),
                RMSNorm(self.eps, name="mtp_enorm")(ahead),
            ], axis=-1).astype(self.dtype)
            x = enter(jnp.dot(joined, _weight(
                self, "mtp_eh_proj", (2 * self.hidden, self.hidden),
                self.dtype)))
        x = block(self.mtp_layer or self.layers[-1], "mtp_layer", x)
        with jax.named_scope(scopes.SEQ_MTP):
            n = RMSNorm(self.eps, name="mtp_norm")(leave(x)).astype(
                self.dtype)
            mtp_logits = jnp.dot(n, head_w,
                                 preferred_element_type=jnp.float32)
        more = {"mtp_logits": mtp_logits}
        if self.mtp_weight is not None:
            more["mtp_loss_weight"] = self.mtp_weight
        return logits, extras(**more)


def chosen_experts(kept: dict) -> dict:
    """``{"layerN": chosen [T, top_k]}`` of every sparse layer, from
    what ``module.apply(..., mutable=["intermediates"])`` kept."""
    return {name: layer["ffn"]["chosen"][0]
            for name, layer in kept["intermediates"].items()
            if "chosen" in layer.get("ffn", {})}


# ------------------------------------------------------------ the class

def layer_specs(kw: dict) -> tuple:
    """The held layers' ``LayerSpec``s from the published keys."""
    hd = kw["head_dim"]
    ropes = {}
    for kind, r in kw["rope_parameters"].items():
        ropes[kind] = Rope(
            kind=r.get("rope_type", "default"),
            theta=float(r["rope_theta"]),
            dims=int(hd * r.get("partial_rotary_factor", 1)),
            factor=float(r.get("factor", 1.0)),
            original=int(r.get("original_max_position_embeddings", 0)),
            beta_fast=float(r.get("beta_fast", 32)),
            beta_slow=float(r.get("beta_slow", 1)),
            attention_factor=float(r.get("attention_factor", 1.0)))
    return tuple(
        LayerSpec(
            heads=int(kw["num_attention_heads_per_layer"][i]),
            window=(int(kw["sliding_window"])
                    if kw["layer_types"][i] == "sliding_attention"
                    else 0),
            rope=ropes[kw["layer_types"][i]],
            sparse=kw["mlp_layer_types"][i] == "sparse")
        for i in range(int(kw["layers_held"])))


def _yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention scale as this family's ``transformers`` code
    has it."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def latent_layer_specs(kw: dict) -> tuple:
    """The held layers' ``LayerSpec``s from the ``xing4_0`` keys
    (the DeepSeek-V3 family's names): every layer latent attention
    over the whole row, ``first_k_dense_replace`` leading layers with
    the dense MLP. The rotary tables carry the ratio of ``mscale`` to
    ``mscale_all_dim``'s scale; the scores carry the latter's
    square."""
    r = kw.get("rope_scaling") or {"type": "default"}
    if r["type"] not in ("default", "yarn"):
        raise ValueError(f"SeqPolicy computes default and yarn rotary "
                         f"only; the spec says {r['type']!r}")
    factor = float(r.get("factor", 1.0))
    over_all = _yarn_mscale(factor, float(r.get("mscale_all_dim", 0)))
    rope = Rope(
        kind=r["type"], theta=float(kw["rope_theta"]),
        dims=int(kw["qk_rope_head_dim"]), factor=factor,
        original=int(r.get("original_max_position_embeddings", 0)),
        beta_fast=float(r.get("beta_fast", 32)),
        beta_slow=float(r.get("beta_slow", 1)),
        attention_factor=_yarn_mscale(factor, float(r.get("mscale", 0)))
        / over_all)
    qk = int(kw["qk_nope_head_dim"]) + int(kw["qk_rope_head_dim"])
    latent = Latent(
        q_rank=int(kw["q_lora_rank"]), kv_rank=int(kw["kv_lora_rank"]),
        nope=int(kw["qk_nope_head_dim"]),
        rope=int(kw["qk_rope_head_dim"]), value=int(kw["v_head_dim"]),
        scale=over_all * over_all / math.sqrt(qk))
    return tuple(
        LayerSpec(heads=int(kw["num_attention_heads"]), window=0,
                  rope=rope,
                  sparse=i >= int(kw["first_k_dense_replace"]),
                  latent=latent)
        for i in range(int(kw["layers_held"])))


def ling_layer_specs(kw: dict) -> tuple:
    """The held layers' ``LayerSpec``s, and the multi-token-
    prediction block's, from the ``bailing_hybrid`` keys: held layer
    ``i`` is the published layer ``i`` (the leading stage) — latent
    attention where ``(i + 1) % layer_group_size == 0``, Kimi delta
    attention elsewhere, the dense MLP in the ``first_k_dense_
    replace`` leading layers. Latent attention has no low-rank query
    path where ``q_lora_rank`` is null, plain rotary on its
    ``qk_rope_head_dim``, and a gate per head; the MTP block is a
    latent-attention + expert layer (``mtp_use_kda: false``)."""
    if kw.get("rope_scaling"):
        raise ValueError("SeqPolicy computes a bailing_hybrid spec's "
                         "rotary unscaled (rope_scaling null) only; "
                         f"the spec says {kw['rope_scaling']!r}")
    held = int(kw["layers_held"])
    for key in ("expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list"):
        if any(kw.get(key, ())[:held]):
            raise ValueError(
                f"SeqPolicy computes an unclamped SwiGLU only; the "
                f"spec's {key} is {kw[key][:held]!r} on the held "
                f"layers")
    heads, hd = int(kw["num_attention_heads"]), int(kw["head_dim"])
    qk = int(kw["qk_nope_head_dim"]) + int(kw["qk_rope_head_dim"])
    rope = Rope(kind="default", theta=float(kw["rope_theta"]),
                dims=int(kw["qk_rope_head_dim"]))
    latent = Latent(
        q_rank=int(kw["q_lora_rank"] or 0),
        kv_rank=int(kw["kv_lora_rank"]),
        nope=int(kw["qk_nope_head_dim"]),
        rope=int(kw["qk_rope_head_dim"]), value=int(kw["v_head_dim"]),
        scale=1.0 / math.sqrt(qk), gate=True)
    kda = Kda(key=hd, value=hd, conv=int(kw["short_conv_kernel_size"]),
              lower=float(kw["kda_lower_bound"]))
    period = int(kw["layer_group_size"])

    def spec(i: int, softmax: bool) -> LayerSpec:
        return LayerSpec(
            heads=heads, window=0, rope=rope,
            sparse=i >= int(kw["first_k_dense_replace"]),
            latent=latent if softmax else None,
            kda=None if softmax else kda)

    return (tuple(spec(i, (i + 1) % period == 0) for i in range(held)),
            spec(held, True))


#: what this decoder computes one way only; a spec that says
#: otherwise is refused rather than run as something else
FIXED = {"attention_bias": False, "gating": "per-head",
         "moe_apply_router_weight_on_input": False,
         "moe_router_logit_softcapping": 0,
         "tie_word_embeddings": False,
         # the xing4_0 keys: an expert layer wherever the dense
         # ones end, the bias-steered choice
         "hidden_act": "silu", "moe_layer_freq": 1,
         "topk_method": "noaux_tc",
         # the bailing_hybrid keys: the safe gate with its lower
         # bound and a full-rank decay projection in the delta
         # layers, SiLU behind their convolutions, L2-normed queries
         # and keys, as many key/value heads as query heads, the
         # output norm per head; latent attention gated per head,
         # its rotary key shared; the MTP block latent; a router
         # bias; no biases, no nGPT, no further norms
         "kda_safe_gate": True, "no_kda_lora": True,
         "use_kda_lora": False, "linear_silu": True,
         "use_qk_norm": True, "num_kv_heads_for_linear_attn": 0,
         "group_norm_size": 1,
         "gated_attention_proj_granularity_type": "head_wise",
         "use_mla_nope": False, "rope_interleave": True,
         "mtp_use_kda": False, "moe_router_enable_expert_bias": True,
         "score_function": "sigmoid", "scale_router_input": False,
         "use_bias": False, "use_qkv_bias": False, "use_nGPT": False,
         "value_norm": False, "up_proj_norm": False}
#: and what it computes in more than one way, by the spec's key
CHOICES = {"model_type": ("laguna", "xing4_0", "bailing_hybrid"),
           "scoring_func": ("softmax", "sigmoid"),
           "num_nextn_predict_layers": (0, 1)}


@neuralnet
class SeqPolicy(NeuralNetBase):
    """The move-sequence policy as a spec-built network: the kwargs
    are the published config's keys plus the held share
    (``layers_held``, ``vocab_held``, ``experts_held``,
    ``expert_offset``). It has no feature planes: its input is id
    rows (``data/convert.py --sequence``)."""

    #: ranks of a training batch's (inputs, labels) — the trainer
    #: shards both over the data axis
    batch_ranks = (2, 2)

    def __init__(self, feature_list=(), *, board: int = 19,
                 init_weights: bool = True, seed: int = 0, **kwargs):
        for key, want in FIXED.items():
            if kwargs.get(key, want) != want:
                raise ValueError(
                    f"SeqPolicy computes {key}={want!r} only; the "
                    f"spec says {kwargs[key]!r}")
        for key, have in CHOICES.items():
            if kwargs.get(key, have[0]) not in have:
                raise ValueError(
                    f"SeqPolicy computes {key} in {have!r} only; the "
                    f"spec says {kwargs[key]!r}")
        if board * board + 2 > int(kwargs["vocab_held"]):
            raise ValueError(
                f"a {board}x{board} record needs {board * board + 2} "
                f"ids; the spec holds {kwargs['vocab_held']}")
        self.feature_list = tuple(feature_list)
        self.board = board
        self.preprocess = None
        self.spec_kwargs = dict(kwargs)
        self.module = self.create_network(**kwargs)
        self.params = None
        if init_weights:
            # with next ids, so that a multi-token-prediction module
            # gets its weights too
            dummy = jnp.zeros((1, 1), jnp.int32)
            self.params = jax.jit(self.module.init)(
                jax.random.key(seed), dummy, dummy)
        self._apply = jax.jit(self.module.apply)

    @property
    def input_planes(self) -> int:
        return 0

    @property
    def num_outputs(self) -> int:
        return int(self.spec_kwargs["vocab_held"])

    def forward(self, ids: jax.Array) -> jax.Array:
        """Logits ``[B, S, vocab_held]`` for id rows ``[B, S]``."""
        return self._apply(self.params, ids)[0]

    @staticmethod
    def create_network(**kw) -> SeqPolicyNet:
        held = {"experts_held": int(kw["experts_held"]),
                "expert_offset": int(kw["expert_offset"]),
                "top_k": int(kw["num_experts_per_tok"]),
                "width": int(kw["moe_intermediate_size"]),
                "norm_topk": bool(kw["norm_topk_prob"]),
                "scoring": kw.get("scoring_func", "softmax")}
        model_type = kw.get("model_type", "laguna")
        mtp_layer = mtp_weight = None
        if model_type == "bailing_hybrid":
            layers, mtp_layer = ling_layer_specs(kw)
            mtp_weight = float(kw["mtp_loss_scaling_factor"])
            ffn = dict(
                held, num_experts=int(kw["num_experts"]),
                shared_width=int(kw["num_shared_experts"])
                * int(kw["moe_shared_expert_intermediate_size"]),
                routed_scale=float(kw["routed_scaling_factor"]))
            kv_heads, head_dim = int(kw["num_attention_heads"]), 0
        elif model_type == "xing4_0":
            layers = latent_layer_specs(kw)
            ffn = dict(
                held, num_experts=int(kw["n_routed_experts"]),
                shared_width=int(kw["n_shared_experts"])
                * int(kw["moe_intermediate_size"]),
                routed_scale=float(kw["routed_scaling_factor"]))
            # one key for each head of the latent layers; no grouping
            kv_heads, head_dim = int(kw["num_attention_heads"]), 0
        else:
            layers = layer_specs(kw)
            ffn = dict(
                held, num_experts=int(kw["num_experts"]),
                shared_width=int(
                    kw["shared_expert_intermediate_size"]),
                routed_scale=float(kw["moe_routed_scaling_factor"]))
            kv_heads = int(kw["num_key_value_heads"])
            head_dim = int(kw["head_dim"])
        if model_type != "laguna":
            # DeepSeek-V3's group-limited choice (one group: none)
            ffn.update(n_group=int(kw.get("n_group", 1)),
                       topk_group=int(kw.get("topk_group", 1)))
        hyper = None
        if "hc_mult" in kw:
            hyper = Hyper(
                streams=int(kw["hc_mult"]),
                iters=int(kw["hc_sinkhorn_iters"]),
                eps=float(kw["hc_eps"]),
                clamp=(float(kw["mhc_h_res_clamp_min"]),
                       float(kw["mhc_h_res_clamp_max"])))
        return SeqPolicyNet(
            layers=layers, hidden=int(kw["hidden_size"]),
            vocab_held=int(kw["vocab_held"]), kv_heads=kv_heads,
            head_dim=head_dim,
            dense_width=int(kw["intermediate_size"]),
            ffn=tuple(sorted(ffn.items())),
            eps=float(kw["rms_norm_eps"]), hyper=hyper,
            mtp=int(kw.get("num_nextn_predict_layers", 0)),
            mtp_layer=mtp_layer, mtp_weight=mtp_weight)
