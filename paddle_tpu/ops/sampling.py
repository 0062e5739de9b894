"""Token sampling: pure decode-time helpers + the `sample_tokens` op.

Decode-time sampling must be *rerun-deterministic*: the same
``(seed, position)`` pair always draws the same token, whether the
token came from a fused K-step `lax.scan` window, K single-step
launches, or a rerun through a restored AOT executable.  The pure
helpers therefore derive one key per absolute sequence position —
``fold_in(key(seed), position)`` — with no stateful key splitting
anywhere, so decode order and batching can never shift the stream
(the contract `tests/test_generation.py` pins bitwise).

``temperature <= 0`` means greedy (argmax); ``top_k > 0`` restricts
the draw to the k highest logits first.  ``top_k`` is a *traced*
value (sort + threshold, not a static lax.top_k call), so one decode
executable serves every per-request k without retracing.  Ties at the
k-th logit all stay eligible — the restriction is "logit >= k-th
highest", the deterministic formulation.  The sort and the draw sit
under `lax.cond`: rows that are all greedy pay for neither (a full sort
of a 261,120-entry vocabulary for 32 slots would cost more than the
rest of a decode step), and a sampled row's token does not depend on
which side its neighbours took.

The `sample_tokens` Program op wires the same math into the graph
runtime: with no explicit ``seed`` attr it draws from ``ctx.rng()``,
which honors the `rng_stream` attr pinned by the optimizer passes —
a rewritten (PT_OPT=1) program samples the same tokens as the raw one.
"""
import jax
import jax.numpy as jnp

from ..core.dtypes import jax_dtype
from ..core.registry import register

_NEG_INF = -1e30

__all__ = ['token_key', 'sample_logits', 'sample_tokens_at']


def token_key(seed, position):
    """The per-token PRNG key: keyed by (request seed, absolute position
    of the token being sampled) and nothing else."""
    return jax.random.fold_in(jax.random.key(seed), position)


def _kth_highest(logits, k):
    """The k-th highest of logits [V] (a full sort: k is traced)."""
    v = logits.shape[-1]
    return (-jnp.sort(-logits, axis=-1))[jnp.clip(k - 1, 0, v - 1)]


def _draw(logits, key, temp, k, thresh):
    """A draw from logits [V] at temperature ``temp`` among the entries
    at or above ``thresh``, the k-th highest; k <= 0 disables the floor."""
    allowed = jnp.where(k > 0, logits >= thresh, True)
    scaled = jnp.where(allowed, logits, _NEG_INF) \
        / jnp.where(temp > 0, temp, 1.0)
    return jax.random.categorical(key, scaled).astype(jnp.int32)


def sample_logits(logits, key, temperature=0.0, top_k=0):
    """One row: logits [V] -> token id (int32).  All args traceable.  A
    greedy row neither sorts nor draws (`lax.cond`; under `vmap` a cond
    computes both sides, so a batch goes through `sample_tokens_at`)."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    k = jnp.clip(jnp.asarray(top_k, jnp.int32), 0, logits.shape[-1])
    temp = jnp.asarray(temperature, jnp.float32)

    def draw():
        thresh = jax.lax.cond(k > 0, lambda: _kth_highest(logits, k),
                              lambda: jnp.float32(0))
        return _draw(logits, key, temp, k, thresh)

    return jax.lax.cond(temp > 0, draw, lambda: greedy)


def sample_tokens_at(logits, seeds, positions, temperatures, top_ks):
    """Batch of independent rows: logits [B, V] with per-row seeds /
    absolute positions / temperatures / top_ks (each [B]).  The batch
    sorts only if some row both samples and restricts (temperature > 0
    and top_k > 0), and draws only if some row samples: a decode step of
    greedy streams is one argmax over the vocabulary.  A row's token is
    the same either way."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ks = jnp.clip(jnp.asarray(top_ks, jnp.int32), 0, logits.shape[-1])
    temps = jnp.asarray(temperatures, jnp.float32)
    sampled = temps > 0

    def draw():
        keys = jax.vmap(token_key)(seeds, positions)
        thresh = jax.lax.cond(
            jnp.any(sampled & (ks > 0)),
            lambda: jax.vmap(_kth_highest)(logits, ks),
            lambda: jnp.zeros(ks.shape, jnp.float32))
        return jax.vmap(_draw)(logits, keys, temps, ks, thresh)

    drawn = jax.lax.cond(jnp.any(sampled), draw, lambda: greedy)
    return jnp.where(sampled, drawn, greedy)


@register('sample_tokens')
def sample_tokens(ctx, ins, attrs):
    logits = ins['Logits']                     # [..., V]
    temp = float(attrs.get('temperature', 0.0))
    top_k = int(attrs.get('top_k', 0))
    seed = int(attrs.get('seed', 0))
    key = jax.random.key(seed) if seed else ctx.rng()
    flat = logits.reshape((-1, logits.shape[-1]))
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        key, jnp.arange(flat.shape[0]))
    out = jax.vmap(sample_logits, (0, 0, None, None))(
        flat, keys, temp, top_k)
    return {'Out': out.reshape(logits.shape[:-1])
            .astype(jax_dtype('int64'))}
