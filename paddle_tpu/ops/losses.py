"""Loss kernels — the cost-layer family.

Reference: paddle/gserver/layers/CostLayer.cpp (MultiClassCrossEntropy,
SoftBinaryClassCrossEntropy, SumOfSquaresCostLayer, RankingCost,
LambdaCost, MultiBinaryLabelCrossEntropy, HuberRegressionLoss,
HuberTwoClassification), CrossEntropyOverBeam, and Gen-2 operators
(softmax_with_cross_entropy, sigmoid_cross_entropy_with_logits, rank_loss,
margin_rank_loss, smooth_l1, squared_l2_distance).

All losses return per-example values [N]; trainers reduce with masks so
variable-length batches weight correctly.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Integer labels; fused log-softmax (reference: classification_cost).

    Always reduces in f32: with bf16 activation storage
    (FLAGS.bf16_dense_activations) a bf16 logsumexp over a 32k vocab loses
    the loss signal's low bits."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
    return logz - picked


def soft_cross_entropy(probs_or_logits: jax.Array, soft_labels: jax.Array,
                       *, from_logits: bool = True) -> jax.Array:
    if from_logits:
        logp = jax.nn.log_softmax(probs_or_logits, axis=-1)
    else:
        logp = jnp.log(jnp.clip(probs_or_logits, 1e-10, 1.0))
    return -jnp.sum(soft_labels * logp, axis=-1)


def sigmoid_cross_entropy_with_logits(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Elementwise then summed over the last dim (reference:
    operators/sigmoid_cross_entropy_with_logits_op.cc)."""
    zeros = jnp.zeros_like(logits)
    loss = jnp.maximum(logits, zeros) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    return jnp.sum(loss, axis=-1)


def multi_binary_label_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Reference: MultiBinaryLabelCrossEntropy (CostLayer.cpp)."""
    return sigmoid_cross_entropy_with_logits(logits, labels)


def square_error(pred: jax.Array, target: jax.Array) -> jax.Array:
    """Sum-of-squares cost, 0.5*||p-t||^2 (reference: SumOfSquaresCostLayer)."""
    d = pred - target
    return 0.5 * jnp.sum(jnp.square(d), axis=tuple(range(1, d.ndim)))


def squared_l2_distance(a: jax.Array, b: jax.Array) -> jax.Array:
    d = a - b
    return jnp.sum(jnp.square(d), axis=-1)


def huber_regression(pred: jax.Array, target: jax.Array, delta: float = 1.0) -> jax.Array:
    """Reference: HuberRegressionLoss (CostLayer.cpp)."""
    d = jnp.abs(pred - target)
    quad = 0.5 * jnp.square(d)
    lin = delta * (d - 0.5 * delta)
    return jnp.sum(jnp.where(d <= delta, quad, lin), axis=-1)


def huber_classification(pred: jax.Array, label01: jax.Array) -> jax.Array:
    """Two-class huber on y∈{-1,1} (reference: HuberTwoClassification)."""
    y = 2.0 * label01.astype(pred.dtype) - 1.0
    z = y * pred[..., 0] if pred.ndim > label01.ndim else y * pred
    loss = jnp.where(z < -1.0, -4.0 * z, jnp.where(z < 1.0, jnp.square(1.0 - z), 0.0))
    return loss


def smooth_l1(pred: jax.Array, target: jax.Array, sigma: float = 1.0) -> jax.Array:
    """Reference: operators/smooth_l1_loss_op.cc."""
    s2 = sigma * sigma
    d = jnp.abs(pred - target)
    loss = jnp.where(d < 1.0 / s2, 0.5 * s2 * jnp.square(d), d - 0.5 / s2)
    return jnp.sum(loss, axis=tuple(range(1, loss.ndim)))


def rank_cost(left: jax.Array, right: jax.Array, label: jax.Array,
              weight: Optional[jax.Array] = None) -> jax.Array:
    """Pairwise ranking cost (reference: RankingCost, CostLayer.cpp):
    C = log(1 + e^{o}) - t*o with o = left - right, t in [0,1]."""
    o = (left - right).reshape(left.shape[0])
    t = label.reshape(label.shape[0]).astype(o.dtype)
    c = jnp.log1p(jnp.exp(-jnp.abs(o))) + jnp.maximum(o, 0.0) - t * o
    if weight is not None:
        c = c * weight.reshape(weight.shape[0])
    return c


def margin_rank_loss(left: jax.Array, right: jax.Array, label: jax.Array,
                     margin: float = 0.0) -> jax.Array:
    """Reference: operators/margin_rank_loss_op.cc: max(0, -l*(x1-x2)+margin)."""
    y = label.reshape(label.shape[0]).astype(left.dtype)
    o = (left - right).reshape(left.shape[0])
    return jnp.maximum(0.0, -y * o + margin)


def cosine_similarity(a: jax.Array, b: jax.Array, scale: float = 1.0,
                      eps: float = 1e-8) -> jax.Array:
    """Reference: CosSimLayer / function/CosSimOp.cpp."""
    num = jnp.sum(a * b, axis=-1)
    den = jnp.sqrt(jnp.sum(a * a, -1) * jnp.sum(b * b, -1) + eps)
    return scale * num / den


def classification_error(logits_or_probs: jax.Array, labels: jax.Array,
                         top_k: int = 1) -> jax.Array:
    """0/1 error per example (reference: ClassificationErrorLayer /
    classification_error_evaluator)."""
    if top_k == 1:
        pred = jnp.argmax(logits_or_probs, axis=-1)
        return (pred != labels.astype(pred.dtype)).astype(jnp.float32)
    _, idx = jax.lax.top_k(logits_or_probs, top_k)
    hit = jnp.any(idx == labels[..., None].astype(idx.dtype), axis=-1)
    return (~hit).astype(jnp.float32)


def cross_entropy_with_selfnorm(logits: jax.Array, labels: jax.Array,
                                alpha: float = 0.1) -> jax.Array:
    """Reference: CrossEntropyWithSelfNorm (CostLayer.cpp): xent + alpha*logZ^2."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
    return (logz - picked) + alpha * jnp.square(logz)


def cross_entropy_over_beam(beams) -> jax.Array:
    """Globally-normalized beam cost for learning-to-search training.

    Reference: paddle/gserver/layers/CrossEntropyOverBeam.cpp:131-162
    (CostForOneSequence::globallyNormalizedScore): each candidate PATH's
    score is the sum of its per-expansion scores, the paths at the
    decisive expansion are softmax-normalized, and the cost is
    -log P(gold path). If gold falls off the beam at expansion t, the
    cost is computed over the beam AT step t; the gold path joins the
    normalizer as an extra path. Gradient flows to EVERY expansion on a
    surviving path (the reference backward()'s addToRows over all
    expansions).

    TPU-native formulation: per expansion the inputs are dense
    (scores[B, N_t], selected[B, K_t], gold[B][, parents[B, K_t]]).
    ``parents`` links candidate k at expansion t to the beam slot at
    t-1 it extends; path scores accumulate along those links. Without
    parents, every candidate extends the gold prefix — the shared
    prefix then cancels in the softmax (and correctly receives zero
    gradient, since d(-log softmax(c+x))/dc = 0). Branch-free: the
    decisive step is selected by index, not control flow.

    Returns per-sequence costs [B].
    """
    neg = -1e9
    kmax = max(int(b[1].shape[1]) for b in beams)
    batch = beams[0][0].shape[0]

    gold_in = []        # [B] per t: gold (with gold ancestry) in beam
    logits_t = []       # [B, Kmax+1] per t: [path scores, gold path]
    path = None         # [B, Kmax] accumulated candidate-path scores
    gold_prefix = jnp.zeros((batch,), beams[0][0].dtype)
    gold_slot_prev = None  # [B] beam slot holding the gold path at t-1

    for b in beams:
        scores, selected, gold = b[0], b[1].astype(jnp.int32), \
            b[2].astype(jnp.int32)
        parents = b[3].astype(jnp.int32) if len(b) > 3 else None
        k = selected.shape[1]
        beam_scores = jnp.take_along_axis(scores, selected, axis=1)
        if path is None or parents is None:
            # first expansion, or unlinked: extend the gold prefix
            path_t = gold_prefix[:, None] + beam_scores
        else:
            path_t = jnp.take_along_axis(path, parents, axis=1) + beam_scores
        gold_score = jnp.take_along_axis(scores, gold[:, None], axis=1)[:, 0]
        gold_prefix = gold_prefix + gold_score
        # the gold PATH sits in the beam only where the candidate id is
        # gold AND (when linked) its ancestry is the gold path's slot
        dup = selected == gold[:, None]
        if parents is not None and gold_slot_prev is not None:
            dup = dup & (parents == gold_slot_prev[:, None])
        gold_slot_prev = jnp.argmax(dup, axis=1)
        gold_in.append(jnp.any(dup, axis=1))
        # mask gold's in-beam copy: it is re-appended as the explicit
        # gold path so it is counted exactly once in the normalizer
        masked = jnp.where(dup, neg, path_t)
        if k < kmax:
            masked = jnp.concatenate(
                [masked, jnp.full((batch, kmax - k), neg, masked.dtype)],
                axis=1)
            path_t = jnp.concatenate(
                [path_t, jnp.full((batch, kmax - k), neg, path_t.dtype)],
                axis=1)
        path = path_t
        logits_t.append(jnp.concatenate([masked, gold_prefix[:, None]],
                                        axis=1))

    gold_in = jnp.stack(gold_in, axis=1)              # [B, T]
    logits = jnp.stack(logits_t, axis=1)              # [B, T, K+1]
    t_count = gold_in.shape[1]
    # decisive expansion: first fall-off, else the last expansion
    fell = jnp.any(~gold_in, axis=1)
    first_off = jnp.argmax(~gold_in, axis=1)
    f = jnp.where(fell, first_off, t_count - 1)       # [B]
    picked = jnp.take_along_axis(
        logits, f[:, None, None], axis=1)[:, 0]       # [B, K+1]
    # gold path is always the LAST logit
    return softmax_cross_entropy(
        picked, jnp.full(picked.shape[:1], picked.shape[1] - 1, jnp.int32))


# ---------------------------------------------------------------------------
# blockwise LM-head cross entropy — flash-style: the [N, V] logits matrix
# never exists in HBM
# ---------------------------------------------------------------------------


def _compute_dtype(x):
    from paddle_tpu.ops.math import compute_dtype  # deferred: avoids a cycle
    return compute_dtype(x)


_PAD_NEG = -1e30   # finite -inf: exp underflows to 0, no NaNs


def _lm_blocks(w, block_v):
    """Resolve (block_v, vocab, n_blocks) with ceil-div blocking: any vocab
    works at full block width — the last block is PADDED (zero weight
    columns, -1e30 bias) rather than shrinking block_v toward 1, which for
    an odd vocab (e.g. 50257) would silently degrade the scan to [N, 1]
    matmuls."""
    v = w.shape[1]
    if block_v <= 0 or block_v > v:
        block_v = v
    nb = -(-v // block_v)
    return block_v, v, nb


def _padded_wb(w, b, bv, nb):
    """Pad w/b out to nb*bv columns: padded logits come out ~-1e30, so
    exp() underflows to exactly 0 in fwd softmax stats and bwd probs."""
    v = w.shape[1]
    pad = nb * bv - v
    if pad == 0:
        return w, b
    wp = jnp.concatenate([w, jnp.zeros((w.shape[0], pad), w.dtype)], axis=1)
    bp = jnp.concatenate([b, jnp.full((pad,), _PAD_NEG, b.dtype)])
    return wp, bp


def lm_head_xent(x, w, b, labels, block_v: int = 4096):
    """loss[i] = logsumexp(x_i @ W + b) - (x_i @ W + b)[labels_i].

    The LM-head fc + softmax_cross_entropy fusion, computed in vocab
    blocks with an online logsumexp (the flash-attention trick applied to
    the classifier): per block only [N, block_v] activations exist, so
    the [N, V] logits (0.5-1 GB at bench shapes) never hit HBM in either
    pass — the backward recomputes each block's softmax from the saved
    logz. Matmuls ride the bf16/f32-accum policy (ops/math.py).

    x: [N, D] tokens; w: [D, V]; b: [V] or None; labels: [N] int.
    Returns per-token loss [N] in f32.  The one loss that holds its own
    head product, so it names its two pieces for a profiler trace:
    ``head.logits`` (the products, forward and backward) and ``head.xent``
    (the reductions and the softmax).
    """
    return _lm_head_xent(x, w, b if b is not None else jnp.zeros(
        (w.shape[1],), jnp.float32), labels.astype(jnp.int32), int(block_v))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _lm_head_xent(x, w, b, labels, block_v):
    loss, _ = _lm_head_fwd_impl(x, w, b, labels, block_v)
    return loss


def _block_logits(x, w, b, j, bv):
    d = w.shape[0]
    with jax.named_scope("head.logits"):
        wj = jax.lax.dynamic_slice(w, (0, j * bv), (d, bv))
        bj = jax.lax.dynamic_slice(b, (j * bv,), (bv,))
        ct = _compute_dtype(x)
        lg = jnp.matmul(x.astype(ct), wj.astype(ct),
                        preferred_element_type=jnp.float32)
        return lg + bj.astype(jnp.float32)


def _lm_head_fwd_impl(x, w, b, labels, block_v):
    bv, v, nb = _lm_blocks(w, block_v)
    w, b = _padded_wb(w, b, bv, nb)
    n = x.shape[0]
    neg = jnp.float32(-jnp.inf)

    def body(carry, j):
        m, s, picked = carry
        lg = _block_logits(x, w, b, j, bv)               # [N, bv] f32
        with jax.named_scope("head.xent"):
            bm = jnp.max(lg, axis=-1)
            new_m = jnp.maximum(m, bm)
            s = s * jnp.exp(m - new_m) + jnp.sum(
                jnp.exp(lg - new_m[:, None]), axis=-1)
            in_blk = (labels >= j * bv) & (labels < (j + 1) * bv)
            idx = jnp.clip(labels - j * bv, 0, bv - 1)
            pick_j = jnp.take_along_axis(lg, idx[:, None], axis=-1)[:, 0]
            picked = jnp.where(in_blk, pick_j, picked)
        return (new_m, s, picked), None

    init = (jnp.full((n,), neg), jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32))
    (m, s, picked), _ = jax.lax.scan(body, init,
                                     jnp.arange(nb, dtype=jnp.int32))
    with jax.named_scope("head.xent"):
        logz = m + jnp.log(s)
        return logz - picked, logz


def _lm_head_xent_fwd(x, w, b, labels, block_v):
    loss, logz = _lm_head_fwd_impl(x, w, b, labels, block_v)
    return loss, (x, w, b, labels, logz)


def _lm_head_xent_bwd(block_v, res, g):
    x, w, b, labels, logz = res
    bv, v, nb = _lm_blocks(w, block_v)
    w, b = _padded_wb(w, b, bv, nb)
    d = w.shape[0]
    gf = g.astype(jnp.float32)

    def body(carry, j):
        dx, dw, db = carry
        lg = _block_logits(x, w, b, j, bv)
        with jax.named_scope("head.xent"):
            p = jnp.exp(lg - logz[:, None])              # softmax block
            in_blk = (labels >= j * bv) & (labels < (j + 1) * bv)
            idx = jnp.clip(labels - j * bv, 0, bv - 1)
            onehot = (jnp.arange(bv)[None, :] == idx[:, None]) \
                & in_blk[:, None]
            dlg = (p - onehot.astype(jnp.float32)) * gf[:, None]  # [N, bv]
        with jax.named_scope("head.logits"):
            wj = jax.lax.dynamic_slice(w, (0, j * bv), (d, bv))
            ct = _compute_dtype(x)
            dx = dx + jnp.matmul(dlg.astype(ct), wj.astype(ct).T,
                                 preferred_element_type=jnp.float32)
            dwj = jnp.matmul(x.astype(ct).T, dlg.astype(ct),
                             preferred_element_type=jnp.float32)
            dw = jax.lax.dynamic_update_slice(
                dw, dwj.astype(dw.dtype), (0, j * bv))
            db = jax.lax.dynamic_update_slice(
                db, jnp.sum(dlg, axis=0).astype(db.dtype), (j * bv,))
        return (dx, dw, db), None

    init = (jnp.zeros(x.shape, jnp.float32), jnp.zeros_like(w),
            jnp.zeros_like(b))
    (dx, dw, db), _ = jax.lax.scan(body, init,
                                   jnp.arange(nb, dtype=jnp.int32))
    # drop the pad columns (grads there are exactly 0 by construction)
    return dx.astype(x.dtype), dw[:, :v], db[:v], None


_lm_head_xent.defvjp(_lm_head_xent_fwd, _lm_head_xent_bwd)
