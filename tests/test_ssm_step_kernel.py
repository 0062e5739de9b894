"""`ssm.ssm_step`, the Pallas kernel of one decode step's recurrence, held
to `ssm.scan_step` (plain jax.numpy over every slot): a live slot's state
of the asked layer advances, everything else of the state array is bit for
bit what it was, a dead slot's y is zero.  Interpret mode on the CPU; the
chip's own compile and run is chip_smoke.py's `kernels` phase.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.parallel import make_mesh
from paddle_tpu.serving.generation import DecodeRuntime, random_weights, ssm

# [slots, layers, heads, head_dim, d_state], groups: the small extents of
# tests/test_generation_ssm.py, and the benchmark cell's tile (8 heads of
# [128, 256] are one block of the kernel)
EXTENTS = {'small': ((3, 2, 6, 16, 8), 2), 'cell_tile': ((4, 2, 8, 128, 256), 2)}
MASKS = {'none': lambda s: np.zeros(s, bool),
         'one': lambda s: np.arange(s) == s - 2,
         'alternating': lambda s: np.arange(s) % 2 == 0,
         'all': lambda s: np.ones(s, bool)}


def _inputs(shape, groups, seed):
    S, L, H, P, N = shape
    rng = np.random.RandomState(seed)
    f32 = np.float32
    return dict(
        x=rng.randn(S, H, P).astype(f32),
        dt=np.log1p(np.exp(rng.randn(S, H))).astype(f32),
        A=-np.exp(rng.randn(H)).astype(f32),
        B=rng.randn(S, groups, N).astype(f32),
        C=rng.randn(S, groups, N).astype(f32),
        D=rng.randn(H).astype(f32),
        state=rng.randn(S, L, H, P, N).astype(f32))


@pytest.mark.parametrize('layer', [0, 1])
@pytest.mark.parametrize('mask', sorted(MASKS))
@pytest.mark.parametrize('extents', sorted(EXTENTS))
def test_the_kernel_is_scan_step_for_the_live_slots_and_nothing_else(
        extents, mask, layer):
    shape, groups = EXTENTS[extents]
    a = _inputs(shape, groups, seed=len(mask) + layer)
    active = MASKS[mask](shape[0])
    step = jax.jit(ssm.ssm_step, donate_argnums=(6,))
    got_y, got_state = step(a['x'], a['dt'], a['A'], a['B'], a['C'], a['D'],
                            jnp.asarray(a['state']), layer,
                            jnp.asarray(active))
    want_y, want_S = ssm.scan_step(a['x'], a['dt'], a['A'], a['B'], a['C'],
                                   a['D'], a['state'][:, layer])
    got_state, got_y = np.asarray(got_state), np.asarray(got_y)
    assert got_state.dtype == np.float32 and got_state.shape == shape
    # every dead slot's state and every OTHER layer's: not touched
    untouched = np.ones(shape[:2], bool)
    untouched[active, layer] = False
    np.testing.assert_array_equal(got_state[untouched],
                                  a['state'][untouched])
    np.testing.assert_allclose(got_state[active, layer],
                               np.asarray(want_S)[active], rtol=1e-6,
                               atol=1e-6)
    # y sums d_state products in another order: 1e-6 of what it sums
    rep = shape[2] // groups
    terms = np.abs(np.asarray(want_S) * np.repeat(
        a['C'], rep, axis=1)[:, :, None, :]).sum(-1) + 1.0
    assert (np.abs(got_y - np.asarray(want_y))[active]
            <= 1e-6 * terms[active]).all()
    assert got_y.shape == shape[:1] + shape[2:4]
    np.testing.assert_array_equal(got_y[~active], 0.0)


def test_the_compacted_order_lists_the_live_slots_first():
    active = jnp.asarray([False, True, True, False, True, False])
    order, count = ssm._live_slots(active)
    assert int(count[0]) == 3 and order.dtype == jnp.int32
    assert np.asarray(order)[:3].tolist() == [1, 2, 4]
    order, count = ssm._live_slots(jnp.zeros(4, bool))
    assert int(count[0]) == 0 and np.asarray(order).tolist() == [0] * 4


@pytest.mark.parametrize('active,revisited', [
    ([False, True, True, False, True], (4, 2)),   # the last live block
    ([True] * 5, None),
    ([False] * 5, (0, 2))])                       # one block, handed back
def test_a_position_past_the_live_count_names_no_new_block(active,
                                                           revisited):
    """What interpret mode cannot show: on the chip a visited block the
    body does not write goes back as garbage, so the dead positions must
    all name the block the last live position wrote."""
    nh = 3
    order, count = ssm._live_slots(jnp.asarray(active))
    live = [s for s, a in enumerate(active) if a]
    blocks = [tuple(int(v) for v in ssm._grid_block(pos, hblk, order, count,
                                                    nh))
              for pos in range(len(active)) for hblk in range(nh)]
    assert blocks[:len(live) * nh] == [(s, hblk) for s in live
                                       for hblk in range(nh)]
    assert set(blocks[len(live) * nh:]) <= {revisited}


@pytest.mark.parametrize('shape,dtype,devices,want', [
    ((3, 2, 6, 16, 8), 'float32', 1, True),
    ((32, 6, 32, 128, 256), 'float32', 1, True),
    ((3, 2, 6, 16, 8), 'bfloat16', 1, False),
    ((3, 2, 6, 16, 8), 'float32', 2, False)])
def test_eligibility_is_read_off_the_state_and_the_mesh(shape, dtype, devices,
                                                        want):
    mesh = None if devices == 1 else make_mesh(
        data=devices, devices=jax.devices()[:devices])
    assert ssm.ssm_step_eligible(shape, dtype, mesh) is want


def test_on_an_accelerator_the_rule_asks_for_whole_tiles(monkeypatch):
    from paddle_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, 'interpret', lambda: False)
    assert ssm.ssm_step_eligible((32, 6, 32, 128, 256), 'float32')
    assert ssm._head_block(32, 128, 256) * 128 * 256 * 4 \
        <= ssm._STEP_BLOCK_BYTES
    assert not ssm.ssm_step_eligible((3, 2, 6, 16, 8), 'float32')   # lanes
    assert not ssm.ssm_step_eligible((3, 2, 8, 12, 128), 'float32')  # rows
    # no block of heads fits the kernel's buffers
    assert not ssm.ssm_step_eligible((2, 1, 3, 1024, 1024), 'float32')


def test_the_composed_route_gives_the_same_tokens():
    """A runtime over a 2-device mesh is not eligible and steps every
    slot with `scan_step`; its streams are the kernel route's."""
    from test_generation_ssm import CFG, CHUNK, WINDOW
    w = random_weights(CFG, seed=3, scale=0.2)
    prompts = [np.random.RandomState(n).randint(1, CFG['vocab'], n)
               .astype(np.int32) for n in (5, 13)]
    kernel = DecodeRuntime(w, CFG, slots=3, prefill_chunk=CHUNK, page_len=4)
    assert kernel.state_kernel
    before = obs.counters()
    composed = DecodeRuntime(
        w, CFG, slots=3, prefill_chunk=CHUNK, page_len=4,
        mesh=make_mesh(data=2, devices=jax.devices()[:2]))
    assert not composed.state_kernel
    want = [kernel.generate(p, 7, steps_per_window=WINDOW) for p in prompts]
    got = [composed.generate(p, 7, steps_per_window=WINDOW) for p in prompts]
    assert got == want
    after = obs.counters()
    assert after['ssm.step_composed'] > before.get('ssm.step_composed', 0)
    # the composed window touched every slot's state
    assert after['generation.state_slot_steps'] \
        - before.get('generation.state_slot_steps', 0) \
        > after['generation.state_live_slot_steps'] \
        - before.get('generation.state_live_slot_steps', 0)
