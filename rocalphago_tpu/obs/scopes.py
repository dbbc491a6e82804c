"""The names of the ``jax.named_scope``s inside the device programs.

A scope is HLO metadata: it changes no computation and costs nothing
at run time, and it is what lets a profiler trace put device time
down to a part of the program (``chipbench/scopes.py`` reduces a
trace by these names; docs/OBSERVABILITY.md lists who reads which).
Every call site imports its constant from here — a scope that is a
string literal elsewhere is a scope no reader can rely on
(``tests/test_scopes.py`` lowers the owning program for each name).

The networks need no entry: Flax scopes every module by its name
(``PolicyNet/trunk/conv3``), and JAX wraps those in ``jvp(…)`` on the
forward and ``transpose(jvp(…))`` on the backward pass of a gradient.
"""

from __future__ import annotations

# ---- the train step (training/sl.py, value.py, rl.py)
#: the input cast and the on-device dihedral augmentation
TRAIN_AUGMENT = "train.augment"
#: the loss after the network's ``apply_fn`` (cross-entropy / MSE /
#: REINFORCE weighting, accuracy)
TRAIN_LOSS = "train.loss"
#: the optimizer: ``tx.update`` + ``apply_updates``
TRAIN_UPDATE = "train.update"

# ---- the move-sequence policy (models/seqpolicy.py). Forward under
# ``jvp(…)``, backward under ``transpose(jvp(…))``; every layer is
# recomputed in the backward pass (``checkpoint``/``rematted_
# computation`` in the path), and that second forward runs under the
# backward's wrapper
#: the embedding lookup (backward: the scatter of its gradient)
SEQ_EMBED = "seq.embed"
#: a full-attention layer's norm, projections, rotary, gate,
#: attention and output projection. Directly under it (and under the
#: two below): the input norm, the residual add and the ``[B,H,S,dv]``
#: → ``[B,S,H,dv]`` transpose of the kernel's output; the rest lies
#: in the five parts ``seq.attn.kernel`` / ``.proj`` / ``.rope`` /
#: ``.gate`` / ``.out``
SEQ_ATTN_FULL = "seq.attn.full"
#: the same of a sliding-window layer
SEQ_ATTN_WINDOW = "seq.attn.window"
#: a latent-attention layer's norms, low-rank projections, rotary,
#: attention and output projection
SEQ_ATTN_MLA = "seq.attn.mla"
#: a Kimi-delta-attention layer's norm and residual, and the three
#: parts below (a reader of ``seq.attn.kda`` sums them)
SEQ_ATTN_KDA = "seq.attn.kda"
#: inside it: the projections of queries, keys, values, decay and
#: ``beta``, the three causal convolutions, SiLU, the L2 norms, the
#: log-decay's gate. Directly under it: the three products ``q_proj``
#: / ``k_proj`` / ``v_proj``; the rest lies in the three parts below
SEQ_ATTN_KDA_PROJ = "seq.attn.kda.proj"
#: inside ``.kda.proj``, since PR 35 two halves of ONE quantity — the
#: pass from a projection's product to the scan's operand
#: (``seqpolicy.kda_mixed``: causal convolution, SiLU, L2 norm, cast;
#: the kernels ``kda_mixed_fwd`` / ``kda_mixed_bwd`` on a TPU) — read
#: them as a sum. This one holds the VALUES' pass, which ends in
#: SiLU: forward, recomputed forward and its backward
SEQ_ATTN_KDA_CONV = "seq.attn.kda.proj.conv"
#: and this one the QUERIES' and the KEYS' passes, which end in the
#: L2 norm over a head (and the queries' ``1/√d_k``): convolution and
#: SiLU included: two tensors to the other's one
SEQ_ATTN_KDA_QKNORM = "seq.attn.kda.proj.norm"
#: ``beta`` (its product and sigmoid) and the log-decay ``g``:
#: ``f_proj``, ``dt_bias``, ``A_log``, the sigmoid
SEQ_ATTN_KDA_DECAY = "seq.attn.kda.proj.decay"
#: from ``q, k, v, g, beta`` to ``o``: the chunked recurrence —
#: the pairwise decays, the inverse, the scan over chunks — forward,
#: recomputed forward and backward
SEQ_ATTN_KDA_SCAN = "seq.attn.kda.scan"
#: the per-head output norm, the channel-wise gate and ``o_proj``
SEQ_ATTN_KDA_OUT = "seq.attn.kda.out"
#: inside any of the softmax three, where the kernel runs: Pallas
#: ``splash_attention`` (forward and backward kernels) and the
#: ``[B,S,H,d]`` → ``[B,H,S,d]`` transposes of q, k, v in front of it
SEQ_ATTN_KERNEL = "seq.attn.kernel"
# The other four parts of a softmax layer (``GatedAttention``,
# ``LatentAttention``), each inside one of the three layer scopes and
# beside the kernel's. A fusion reads under the scope of the dot
# inside it, else its root's (``chipbench/scopes.py::resolve``): an
# elementwise pass XLA fuses into a product reads under the product's
# name
#: the MXU work in front of the kernel: the products from ``x`` (q, k,
#: v) and their weights' casts; latent attention's low-rank products
#: and their two norms
SEQ_ATTN_PROJ = "seq.attn.proj"
#: the float32 rotary passes over ``[B, S, H, d]``; in latent
#: attention also the scaling of ``q_nope``, the broadcast of ``k_pe``
#: to the heads and the two concatenations that build q and k
SEQ_ATTN_ROPE = "seq.attn.rope"
#: the per-head gate: ``gate_proj``'s product from ``x``, the sigmoid
#: and the product with the kernel's output
SEQ_ATTN_GATE = "seq.attn.gate"
#: the reshape and ``o_proj``
SEQ_ATTN_OUT = "seq.attn.out"
#: hyper-connections, three scopes side by side (a reader of
#: ``seq.mhc`` sums them). The coefficients of one sublayer: the
#: streams' norm statistic, their product with the three ``phi``s,
#: the sigmoids
SEQ_MHC_COEFF = "seq.mhc.coeff"
#: the Sinkhorn iterations that make ``H_res`` doubly stochastic
SEQ_MHC_SINKHORN = "seq.mhc.sinkhorn"
#: the passes over the streams: ``H_pre X`` into a sublayer,
#: ``H_res X + H_post^T y`` out of it, and the streams' sum at the end
SEQ_MHC_MIX = "seq.mhc.mix"
#: what the multi-token-prediction module adds outside its block (the
#: block runs under the layer scopes above): the next ids' embedding,
#: the two norms, ``eh_proj``, its final norm and its head product
SEQ_MTP = "seq.mtp"
#: the norm before a sparse layer, the float32 router, top-k and
#: weights
SEQ_ROUTER = "seq.router"
#: the expert layer: the four parts below, the held experts'
#: matrices cast to the compute type, and the grouped products
#: (which XLA renames: ``chipbench/seq_readers.py``)
SEQ_EXPERTS = "seq.experts"
#: inside it (forward, recomputed forward and the backward rules
#: alike): sorting the pairs by held expert, their counts and offsets
SEQ_EXPERTS_SORT = "seq.experts.sort"
#: tokens' rows into the buffer, in row blocks that hold a pair;
#: backward: on those blocks, the two products' cotangents summed and
#: added to their tokens' rows
SEQ_EXPERTS_DISPATCH = "seq.experts.dispatch"
#: between the products, in the same blocks: float32 SwiGLU inside
SEQ_EXPERTS_ACT = "seq.experts.act"
#: on the same blocks: the pairs' results times their weights, added
#: to their tokens' rows; backward: the cotangent's rows into the
#: buffer and each pair's weight's gradient, from one gather a block
SEQ_EXPERTS_COMBINE = "seq.experts.combine"
#: the shared expert
SEQ_SHARED = "seq.shared"
#: the dense MLP of a layer without experts, with its norm
SEQ_DENSE_FFN = "seq.dense_ffn"
#: the final norm and the output head
SEQ_HEAD = "seq.head"

# ---- one self-play ply (search/selfplay.py::_make_ply)
#: the shared group analysis (``vgroup_data``)
PLY_GROUPS = "ply.groups"
#: feature planes from states (``features/``; holds the ``encode.*``)
PLY_ENCODE = "ply.encode"
#: both half-batch network forwards and the half swap
PLY_FORWARD = "ply.forward"
#: sensibleness mask, temperature, categorical draw
PLY_SAMPLE = "ply.sample"
#: the rules step (``engine/jaxgo.py::step`` under vmap)
PLY_STEP = "ply.step"

# ---- the stages of any encode (features/planes.py, incremental.py)
#: the per-candidate-move analysis (``encode_analysis``): captures,
#: merged groups and liberties after each move, and legality — what
#: the capture-size, self-atari and liberties-after planes read
ENCODE_CANDIDATES = "encode.candidates"
#: the ladder capture/escape planes: the bounded chase
ENCODE_LADDER = "encode.ladder"
#: stacking the plane groups (``assemble_planes``): stones, ages,
#: liberties, the one-hots of the analysis, sensibleness (true eyes)
ENCODE_PLANES = "encode.planes"

# ---- a leaf evaluation (search/device_mcts.py::eval_batch*)
#: the group analysis of the leaves
EVAL_GROUPS = "eval.groups"
#: feature planes for both nets
EVAL_ENCODE = "eval.encode"
#: the policy forward, masking and softmax to priors
EVAL_POLICY = "eval.policy"
#: the value forward (and terminal overrides)
EVAL_VALUE = "eval.value"

# ---- one tree simulation (search/device_mcts.py)
#: the PUCT descent to a leaf (``_descend_one`` via ``prepare_sim``)
MCTS_SELECT = "mcts.select"
#: stepping the leaf's state and writing the new node (``apply_sim``
#: up to the backup)
MCTS_EXPAND = "mcts.expand"
#: the value's walk back to the root (``_backup_one``)
MCTS_BACKUP = "mcts.backup"

#: every name above, for tests and readers
ALL = tuple(v for k, v in sorted(globals().items())
            if k.isupper() and isinstance(v, str))
