"""Plain reference for `resnet50`: ResNet-50 of arXiv:1512.03385 (Table 1,
bottleneck blocks, the stride on the first 1x1 as in the paper) as
models/resnet.py builds it, forward pass and loss in jax.numpy, float32,
precision `highest`.  Batch normalisation in training mode: statistics of
the batch itself, biased variance, epsilon 1e-5; the loss is the mean
cross entropy of the softmax output.  Parameters are found by the names
the program's builder gives them, in creation order (conv2d_<i>.w_0,
batch_norm_<i>.w_0 / .b_0, fc_0.w_0 / .b_0): within a block the shortcut
is created first.

LOSS_RTOL: the program runs its convolutions in bf16 with f32 batch
statistics, the reference in f32.  Each of the 53 normalisations rescales
to unit variance, so rounding does not grow with depth: the first step's
loss, a mean over 128 images of 7.4 to 8.0 (ln 1000 = 6.9 plus what the
random head adds, so it depends on every layer), differed from the
reference's by 3.0e-4 to 4.4e-3 relative over 15 runs on the chip (my chip
runs, PR 23).  1e-2 is 2.3 times the largest seen, and below what a wrong
stride, a skipped shortcut or inference-mode statistics move it by (3e-2
and up).

TRAINED_RTOL is None: after the window the net has memorised the pool's
2,048 random images (loss 0.002 to 0.15) and sits where one bf16 rounding
moves whole images across the decision boundary; there the program and the
reference differed by 0.1 % to 71 % (same runs), which measures how sharp
an overfit net is and not an error.  The trained loss is reported, not
judged.
"""
import jax
import jax.numpy as jnp

LOSS_RTOL = 1e-2
TRAINED_RTOL = None
BN_EPS = 1e-5
_STAGES = {50: [3, 4, 6, 3], 101: [3, 4, 23, 3], 152: [3, 8, 36, 3]}


class _Params(object):
    """Hands out conv and batch-norm parameters in creation order."""

    def __init__(self, params):
        self.p, self.i = params, 0

    def conv_bn(self, x, stride, pad, relu):
        w = self.p['conv2d_%d.w_0' % self.i]
        g = self.p['batch_norm_%d.w_0' % self.i]
        b = self.p['batch_norm_%d.b_0' % self.i]
        self.i += 1
        y = jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
        m = jnp.mean(y, (0, 2, 3), keepdims=True)
        v = jnp.mean(jnp.square(y - m), (0, 2, 3), keepdims=True)
        y = (y - m) * jax.lax.rsqrt(v + BN_EPS) * g.reshape(1, -1, 1, 1) \
            + b.reshape(1, -1, 1, 1)
        return jax.nn.relu(y) if relu else y


def _bottleneck(ps, x, width, stride):
    short = x
    if x.shape[1] != width * 4:
        short = ps.conv_bn(x, stride, 0, False)
    y = ps.conv_bn(x, stride, 0, True)
    y = ps.conv_bn(y, 1, 1, True)
    y = ps.conv_bn(y, 1, 0, False)
    return jax.nn.relu(short + y)


def _forward(params, data, label, depth):
    ps = _Params(params)
    x = ps.conv_bn(data, 2, 3, True)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              [(0, 0), (0, 0), (1, 1), (1, 1)])
    for stage, (n, width) in enumerate(zip(_STAGES[depth],
                                           (64, 128, 256, 512))):
        for block in range(n):
            x = _bottleneck(ps, x, width,
                            2 if (block == 0 and stage > 0) else 1)
    x = jnp.mean(x, (2, 3))
    logits = x @ params['fc_0.w_0'] + params['fc_0.b_0']
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, label, -1))


def loss(params, feed, config):
    fwd = jax.jit(_forward, static_argnums=(3,))
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision('highest'):
        return float(fwd(p, jnp.asarray(feed['data'], jnp.float32),
                         jnp.asarray(feed['label']), int(config['depth'])))
