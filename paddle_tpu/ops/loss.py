"""Loss ops.

Parity: reference cross_entropy_op, softmax_with_cross_entropy_op,
squared_l2/smooth_l1/huber/log/rank/margin_rank/bpr loss ops, nce_op,
hsigmoid_op, sigmoid_cross_entropy_with_logits_op.
"""
import numpy as np
import jax
import jax.numpy as jnp

from ..core.registry import register

_EPS = 1e-8


def _squeeze_label(label):
    if label.ndim >= 2 and label.shape[-1] == 1:
        return label[..., 0]
    return label


@register('cross_entropy')
def cross_entropy(ctx, ins, attrs):
    # log/sum in f32 regardless of input dtype (AMP feeds bf16 probs);
    # the per-row loss is always f32 so downstream reductions stay exact
    x, label = ins['X'].astype(jnp.float32), ins['Label']
    if attrs.get('soft_label', False):
        out = -jnp.sum(label.astype(jnp.float32) * jnp.log(x + _EPS),
                       axis=-1, keepdims=True)
        return {'Y': out}
    lab = _squeeze_label(label)
    picked = jnp.take_along_axis(x, lab[..., None].astype(jnp.int32),
                                 axis=-1)
    ignore = attrs.get('ignore_index', -100)
    out = -jnp.log(picked + _EPS)
    out = jnp.where(lab[..., None] == ignore, jnp.zeros_like(out), out)
    return {'Y': out}


import functools


@functools.lru_cache(maxsize=None)
def _make_hard_ce(V, eps, ignore):
    """Efficient hard-label CE with a hand-written vjp (per-HLO profile,
    PERF.md r5): JAX autodiff of the logsumexp chain materialized the
    dlogits cotangent as an f32 [B, T, V] buffer (1 GB at bench shapes)
    plus a separate log_softmax backward reduction pass.  Here the
    residuals are just (logits, label, lse[B,T,1]); the backward
    computes dlogits = g * (softmax - (1-eps)*onehot - eps/V) in ONE
    fused elementwise pass and emits it in the LOGITS dtype — bf16 when
    the projection flows through under AMP, so the two backward GEMMs
    read half the bytes.  Numerics: all reductions and the stored lse
    are f32 regardless of logits dtype (same contract as before); the
    bf16 rounding of dlogits is the same rounding the MXU applied to
    the f32 cotangent anyway."""

    @jax.custom_vjp
    def ce(logits, lab):
        return _fwd(logits, lab)[0]

    def _fwd(logits, lab):
        x = logits.astype(jnp.float32)
        m = jnp.max(x, axis=-1, keepdims=True)
        lse = m + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True))
        # gather from the UNconverted logits: XLA can fuse a convert
        # into reduce fusions but not into the gather's kCustom call, so
        # take_along_axis(x, ...) forced a full f32 [B, T, V]
        # materialization just to pick B*T scalars (per-HLO ledger,
        # PERF.md r5); converting the picked values is identical math
        tgt = jnp.take_along_axis(logits, lab, axis=-1).astype(jnp.float32)
        if eps:
            # (1-eps)*hard_ce + eps*(-mean logp), closed form
            loss = lse - (1.0 - eps) * tgt - eps * jnp.mean(
                x, axis=-1, keepdims=True)
        else:
            loss = lse - tgt
        loss = jnp.where(lab == ignore, jnp.zeros_like(loss), loss)
        return loss, (logits, lab, lse)

    def bwd(res, g):
        logits, lab, lse = res
        x = logits.astype(jnp.float32)
        p = jnp.exp(x - lse)
        onehot = (jnp.arange(V) == lab).astype(jnp.float32)
        d = p - (1.0 - eps) * onehot - (eps / V)
        gz = jnp.where(lab == ignore, jnp.zeros_like(g),
                       g.astype(jnp.float32))
        dlogits = (gz * d).astype(logits.dtype)
        return dlogits, np.zeros(lab.shape, jax.dtypes.float0)

    ce.defvjp(_fwd, bwd)
    return ce


@register('softmax_with_cross_entropy')
def softmax_with_cross_entropy(ctx, ins, attrs):
    # logsumexp in f32 (bf16 logits under AMP are fine — the reduction is
    # not); Loss is always f32.  Hard labels over the last axis take the
    # custom-vjp fast path (_make_hard_ce).
    logits, label = ins['Logits'], ins['Label']
    axis = attrs.get('axis', -1)
    ndim = logits.ndim
    if not attrs.get('soft_label', False) and axis in (-1, ndim - 1):
        lab = label
        if lab.ndim == ndim - 1:
            lab = jnp.expand_dims(lab, -1)
        lab = lab.astype(jnp.int32)
        ce = _make_hard_ce(int(logits.shape[-1]),
                           float(attrs.get('label_smooth_eps', 0.0)),
                           int(attrs.get('ignore_index', -100)))
        loss = ce(logits, lab)
        # derived lazily so an unused Softmax output DCEs away with its
        # whole log_softmax chain (the common training case)
        softmax = jnp.exp(jax.nn.log_softmax(
            logits.astype(jnp.float32), axis=-1))
        return {'Loss': loss, 'Softmax': softmax}
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
    if attrs.get('soft_label', False):
        loss = -jnp.sum(label.astype(jnp.float32) * logp, axis=axis,
                        keepdims=True)
    else:
        # label keeps a size-1 dim at `axis` (reference convention); add it
        # if the caller passed the squeezed form
        lab = label
        if lab.ndim == logp.ndim - 1:
            lab = jnp.expand_dims(lab, axis)
        picked = jnp.take_along_axis(logp, lab.astype(jnp.int32), axis=axis)
        loss = -picked
        eps = attrs.get('label_smooth_eps', 0.0)
        if eps:
            # fused uniform label smoothing: -sum(soft*logp) with
            # soft = (1-eps)*onehot + eps/V equals
            # (1-eps)*hard_ce + eps*(-mean(logp)) — the [.., V] one-hot /
            # smoothed-label tensors never materialize, and AD yields the
            # same softmax-minus-soft gradient
            loss = (1.0 - eps) * loss + eps * (
                -jnp.mean(logp, axis=axis, keepdims=True))
        ignore = attrs.get('ignore_index', -100)
        loss = jnp.where(lab == ignore, jnp.zeros_like(loss), loss)
    return {'Loss': loss, 'Softmax': jnp.exp(logp)}


@register('square_error_cost')
def square_error_cost(ctx, ins, attrs):
    return {'Out': jnp.square(ins['X'] - ins['Y'])}


@register('smooth_l1_loss')
def smooth_l1_loss(ctx, ins, attrs):
    x, y = ins['X'], ins['Y']
    sigma = attrs.get('sigma', 1.0)
    s2 = sigma * sigma
    diff = x - y
    if 'InsideWeight' in ins:
        diff = diff * ins['InsideWeight']
    ad = jnp.abs(diff)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * s2 * jnp.square(diff),
                     ad - 0.5 / s2)
    if 'OutsideWeight' in ins:
        loss = loss * ins['OutsideWeight']
    return {'Out': jnp.sum(loss, axis=tuple(range(1, loss.ndim)),
                           keepdims=False).reshape(-1, 1),
            'Diff': diff}


@register('huber_loss')
def huber_loss(ctx, ins, attrs):
    x, y = ins['X'], ins['Y']
    d = attrs.get('delta', 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= d, 0.5 * jnp.square(r), d * (ar - 0.5 * d))
    return {'Out': loss, 'Residual': r}


@register('log_loss')
def log_loss(ctx, ins, attrs):
    p, label = ins['Predicted'], ins['Labels']
    eps = attrs.get('epsilon', 1e-4)
    out = -label * jnp.log(p + eps) - (1 - label) * jnp.log(1 - p + eps)
    return {'Loss': out}


@register('rank_loss')
def rank_loss(ctx, ins, attrs):
    label, left, right = ins['Label'], ins['Left'], ins['Right']
    d = left - right
    out = jnp.log1p(jnp.exp(d)) - label * d
    return {'Out': out}


@register('margin_rank_loss')
def margin_rank_loss(ctx, ins, attrs):
    label, x1, x2 = ins['Label'], ins['X1'], ins['X2']
    m = attrs.get('margin', 0.0)
    out = jnp.maximum(0.0, -label * (x1 - x2) + m)
    return {'Out': out, 'Activated': (out > 0).astype(x1.dtype)}


@register('bpr_loss')
def bpr_loss(ctx, ins, attrs):
    x, label = ins['X'], ins['Label']  # x: [N, C] logits
    lab = _squeeze_label(label).astype(jnp.int32)
    n, c = x.shape
    pos = jnp.take_along_axis(x, lab[:, None], axis=1)
    diff = pos - x  # [N, C]
    lse = -jnp.log(jax.nn.sigmoid(diff) + _EPS)
    mask = 1.0 - jax.nn.one_hot(lab, c, dtype=x.dtype)
    out = jnp.sum(lse * mask, axis=1, keepdims=True) / (c - 1)
    return {'Y': out}


@register('sigmoid_cross_entropy_with_logits')
def sigmoid_cross_entropy_with_logits(ctx, ins, attrs):
    x, label = ins['X'], ins['Label']
    ignore = attrs.get('ignore_index', -100)
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    loss = jnp.where(label == ignore, jnp.zeros_like(loss), loss)
    if attrs.get('normalize', False):
        cnt = jnp.sum((label != ignore).astype(x.dtype))
        loss = loss / jnp.maximum(cnt, 1.0)
    return {'Out': loss}


@register('teacher_student_sigmoid_loss')
def teacher_student_sigmoid_loss(ctx, ins, attrs):
    x, label = ins['X'], ins['Label']
    soft_max_up = attrs.get('soft_max_up_bound', 15.0)
    soft_max_lo = attrs.get('soft_max_lower_bound', -15.0)
    z = jnp.clip(x, soft_max_lo, soft_max_up)
    # teacher (soft) part + student (hard) part, ref
    # teacher_student_sigmoid_loss_op.cc
    out = jnp.log1p(jnp.exp(-jnp.abs(z))) + jnp.maximum(z, 0) - z * label
    return {'Y': out}


@register('kldiv_loss')
def kldiv_loss(ctx, ins, attrs):
    x, target = ins['X'], ins['Target']
    loss = target * (jnp.log(target + _EPS) - x)
    red = attrs.get('reduction', 'mean')
    if red == 'mean':
        loss = jnp.mean(loss).reshape(1)
    elif red == 'sum':
        loss = jnp.sum(loss).reshape(1)
    elif red == 'batchmean':
        loss = (jnp.sum(loss) / x.shape[0]).reshape(1)
    return {'Loss': loss}


@register('nce')
def nce(ctx, ins, attrs):
    """Noise-contrastive estimation (ref nce_op.cc).  TPU-native: sampled
    softmax with uniform negative sampling, fully batched."""
    x, w, label = ins['Input'], ins['Weight'], ins['Label']
    num_neg = attrs.get('num_neg_samples', 10)
    num_classes = attrs.get('num_total_classes')
    lab = _squeeze_label(label).astype(jnp.int32)
    b = x.shape[0]
    key = ctx.rng()
    neg = jax.random.randint(key, (b, num_neg), 0, num_classes)
    ids = jnp.concatenate([lab[:, None], neg], axis=1)  # [B, 1+K]
    wsel = jnp.take(w, ids, axis=0)  # [B, 1+K, D]
    logits = jnp.einsum('bd,bkd->bk', x, wsel)
    if 'Bias' in ins:
        logits = logits + jnp.take(ins['Bias'], ids, axis=0).reshape(
            logits.shape)
    labels01 = jnp.concatenate(
        [jnp.ones((b, 1)), jnp.zeros((b, num_neg))], axis=1)
    loss = jnp.maximum(logits, 0) - logits * labels01 + \
        jnp.log1p(jnp.exp(-jnp.abs(logits)))
    return {'Cost': jnp.sum(loss, axis=1, keepdims=True),
            'SampleLogits': logits, 'SampleLabels': ids}


@register('hierarchical_sigmoid')
def hierarchical_sigmoid(ctx, ins, attrs):
    """hsigmoid (ref hierarchical_sigmoid_op.cc) with a complete binary
    tree over classes."""
    x, w, label = ins['X'], ins['W'], ins['Label']
    num_classes = attrs.get('num_classes')
    code_len = int(np.ceil(np.log2(max(num_classes, 2))))
    lab = _squeeze_label(label).astype(jnp.int32)
    # path of internal nodes for each class in a complete binary tree
    codes = []
    bits = []
    node = lab + num_classes  # leaves occupy [num_classes, 2*num_classes)
    for _ in range(code_len):
        parent = node // 2
        bit = (node % 2).astype(x.dtype)
        codes.append(parent - 1)  # internal nodes indexed from 1
        bits.append(bit)
        node = parent
    codes = jnp.stack(codes, axis=1)  # [B, L]
    bits = jnp.stack(bits, axis=1)
    codes = jnp.clip(codes, 0, w.shape[0] - 1)
    wsel = jnp.take(w, codes, axis=0)  # [B, L, D]
    logits = jnp.einsum('bd,bld->bl', x, wsel)
    if 'Bias' in ins:
        logits = logits + jnp.take(ins['Bias'].reshape(-1), codes, axis=0)
    loss = jnp.maximum(logits, 0) - logits * bits + \
        jnp.log1p(jnp.exp(-jnp.abs(logits)))
    return {'Out': jnp.sum(loss, axis=1, keepdims=True),
            'PreOut': logits}


@register('dice_loss')
def dice_loss(ctx, ins, attrs):
    # implemented at layer level in reference too; kept as op for parity
    x, label = ins['X'], ins['Label']
    eps = attrs.get('epsilon', 1e-5)
    label = label.astype(x.dtype)
    inter = 2.0 * jnp.sum(x * label, axis=tuple(range(1, x.ndim)))
    union = jnp.sum(x, axis=tuple(range(1, x.ndim))) + \
        jnp.sum(label, axis=tuple(range(1, x.ndim)))
    return {'Out': (1.0 - inter / (union + eps)).reshape(-1, 1)}
