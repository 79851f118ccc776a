"""Backend-parity harness for the event-voxelization front-end: the
Pallas kernel (interpret mode) must be BIT-IDENTICAL to the pure-jnp
reference (`repro.core.encoding.events_to_voxel`) across modes, oob
policies, ragged valid-masks, out-of-range coordinates/timestamps, and
empty streams.  Differential style: same inputs through both backends,
`assert_array_equal` (never allclose — counts are exact integers in
f32).

Plain parametrized sweeps always run; the hypothesis fuzz layer rides
on top when hypothesis is installed (CI tier-2 lane).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.encoding import (EventStream, events_to_voxel,
                                 events_to_voxel_batch, voxel_batch)
from repro.kernels import ops, ref
from repro.kernels.event_voxel import MODES, OOB_POLICIES, event_voxel_pallas

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

T, H, W = 5, 16, 12


def _stream(seed, batch=2, n=96, ragged=0.7, oob_frac=True):
    """Random batched stream with ragged masks and (optionally)
    out-of-range coordinates/timestamps/polarities."""
    rng = np.random.default_rng(seed)
    lo_x, hi_x = (-3, W + 3) if oob_frac else (0, W)
    lo_y, hi_y = (-3, H + 3) if oob_frac else (0, H)
    t_lo, t_hi = (-0.4, 1.5) if oob_frac else (0.0, 1.0)
    return EventStream(
        t=jnp.asarray(rng.uniform(t_lo, t_hi, (batch, n)).astype(np.float32)),
        x=jnp.asarray(rng.integers(lo_x, hi_x, (batch, n)), jnp.int32),
        y=jnp.asarray(rng.integers(lo_y, hi_y, (batch, n)), jnp.int32),
        p=jnp.asarray(rng.integers(-1 if oob_frac else 0, 3 if oob_frac else 2,
                                   (batch, n)), jnp.int32),
        valid=jnp.asarray(rng.random((batch, n)) < ragged))


def _pallas(ev, **kw):
    return ops.event_voxel_op(ev, time_steps=T, height=H, width=W, **kw)


def _jnp(ev, **kw):
    return ref.event_voxel_ref(ev, time_steps=T, height=H, width=W, **kw)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("oob", OOB_POLICIES)
def test_backend_parity_all_modes(mode, oob):
    ev = _stream(seed=MODES.index(mode) * 10 + OOB_POLICIES.index(oob))
    got = _pallas(ev, mode=mode, oob=oob)
    want = _jnp(ev, mode=mode, oob=oob)
    assert got.shape == want.shape == (2, T, H, W, 2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block_t", [1, 2, 3, T, T + 3, 0])
def test_time_blocked_grid_invariant(block_t):
    """The time-blocked scatter must not depend on the slab size."""
    ev = _stream(seed=7)
    base = _jnp(ev, mode="count")
    got = _pallas(ev, mode="count", block_t=block_t)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_empty_stream_is_zero_grid():
    ev = _stream(seed=3, ragged=0.0)           # every event masked out
    for mode in MODES:
        got = _pallas(ev, mode=mode)
        assert float(jnp.abs(got).sum()) == 0.0
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(_jnp(ev, mode=mode)))


def test_single_sample_reference_consistency():
    """events_to_voxel (single window) == batched reference == kernel."""
    ev = _stream(seed=11, batch=3)
    want = events_to_voxel_batch(ev, time_steps=T, height=H, width=W,
                                 mode="count")
    one = jnp.stack([
        events_to_voxel(jax.tree_util.tree_map(lambda a: a[i], ev),
                        time_steps=T, height=H, width=W, mode="count")
        for i in range(3)])
    np.testing.assert_array_equal(np.asarray(want), np.asarray(one))
    tm = voxel_batch(ev, time_steps=T, height=H, width=W, mode="count")
    np.testing.assert_array_equal(np.asarray(tm),
                                  np.asarray(jnp.moveaxis(want, 0, 1)))


def test_boundary_timestamp_policy_explicit():
    """The seed aliased t == window into the last bin silently; the
    policy is now explicit: "clip" keeps that aliasing, "drop" discards
    the event — on BOTH backends."""
    def one(tval):
        return EventStream(t=jnp.full((1, 1), tval, jnp.float32),
                           x=jnp.full((1, 1), 2, jnp.int32),
                           y=jnp.full((1, 1), 3, jnp.int32),
                           p=jnp.ones((1, 1), jnp.int32),
                           valid=jnp.ones((1, 1), bool))

    for fn in (_pallas, _jnp):
        at_window = fn(one(1.0), mode="count", oob="clip")
        assert float(at_window[0, T - 1, 3, 2, 1]) == 1.0   # aliased in
        assert float(at_window.sum()) == 1.0
        assert float(fn(one(1.0), mode="count", oob="drop").sum()) == 0.0
        before_zero = fn(one(-0.3), mode="count", oob="clip")
        assert float(before_zero[0, 0, 3, 2, 1]) == 1.0     # aliased to bin 0
        assert float(fn(one(-0.3), mode="count", oob="drop").sum()) == 0.0
        # strictly interior timestamps are policy-independent
        np.testing.assert_array_equal(
            np.asarray(fn(one(0.5), mode="count", oob="clip")),
            np.asarray(fn(one(0.5), mode="count", oob="drop")))


def test_signed_mode_channels():
    """signed mode: channel 0 = ON - OFF, channel 1 = ON + OFF."""
    ev = _stream(seed=5, oob_frac=False)
    cnt = _pallas(ev, mode="count")
    sgn = _pallas(ev, mode="signed")
    np.testing.assert_array_equal(np.asarray(sgn[..., 0]),
                                  np.asarray(cnt[..., 1] - cnt[..., 0]))
    np.testing.assert_array_equal(np.asarray(sgn[..., 1]),
                                  np.asarray(cnt[..., 1] + cnt[..., 0]))
    np.testing.assert_array_equal(
        np.asarray(_pallas(ev, mode="binary")),
        np.asarray((cnt > 0).astype(jnp.float32)))


def test_pad_stream_batched_pads_capacity_axis_only():
    """Regression: padding a [B, N] stream must grow N, never B."""
    from repro.core.encoding import fit_stream, pad_stream
    ev = _stream(seed=2, batch=2, n=10)
    out = pad_stream(ev, 32)
    assert out.t.shape == (2, 32)
    assert int(out.num_events().sum()) == int(ev.num_events().sum())
    assert not bool(out.valid[:, 10:].any())
    same = fit_stream(ev, 10)
    assert same.t.shape == (2, 10)
    np.testing.assert_array_equal(np.asarray(
        _jnp(out, mode="count")), np.asarray(_jnp(ev, mode="count")))


def test_budget_events_batched_per_window():
    """Regression: budgeting a [B, N] stream compacts per window (the
    path pad_stream's error message sends batched callers down)."""
    from repro.core.encoding import budget_events, fit_stream
    ev = _stream(seed=4, batch=3, n=40, ragged=1.0)
    out = budget_events(ev, 8)
    assert out.t.shape == (3, 8)
    for b in range(3):
        kept = np.sort(np.asarray(out.t[b][out.valid[b]]))
        all_t = np.sort(np.asarray(ev.t[b][ev.valid[b]]))
        np.testing.assert_array_equal(kept, all_t[:8])
    sub = budget_events(ev, 8, rng=jax.random.PRNGKey(0))
    assert sub.t.shape == (3, 8) and int(sub.num_events().sum()) == 24
    assert fit_stream(ev, 8).t.shape == (3, 8)      # batched overfull fit


def test_invalid_args_rejected():
    ev = _stream(seed=1)
    with pytest.raises(ValueError, match="mode"):
        event_voxel_pallas(ev.t, ev.x, ev.y, ev.p,
                           ev.valid.astype(jnp.int32), time_steps=T,
                           height=H, width=W, mode="typo", interpret=True)
    with pytest.raises(ValueError, match="oob"):
        events_to_voxel(jax.tree_util.tree_map(lambda a: a[0], ev),
                        time_steps=T, height=H, width=W, oob="typo")
    with pytest.raises(ValueError, match="mode"):
        events_to_voxel(jax.tree_util.tree_map(lambda a: a[0], ev),
                        time_steps=T, height=H, width=W, mode="typo")


@pytest.mark.slow
@pytest.mark.parametrize("shape", [(1, 1), (1, 300), (4, 257), (2, 1024)])
@pytest.mark.parametrize("tsteps", [1, 4, 9])
def test_backend_parity_shape_sweep(shape, tsteps):
    B, N = shape
    ev = _stream(seed=B * 1000 + N + tsteps, batch=B, n=N)
    for mode in MODES:
        got = ops.event_voxel_op(ev, time_steps=tsteps, height=H, width=W,
                                 mode=mode, oob="drop", block_t=2)
        want = ref.event_voxel_ref(ev, time_steps=tsteps, height=H, width=W,
                                   mode=mode, oob="drop")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**20), n=st.integers(1, 128),
           batch=st.integers(1, 3), ragged=st.floats(0.0, 1.0),
           mode=st.sampled_from(MODES), oob=st.sampled_from(OOB_POLICIES),
           block_t=st.integers(0, T + 2))
    def test_fuzz_backend_parity(seed, n, batch, ragged, mode, oob,
                                 block_t):
        """Hypothesis-driven differential fuzz: any stream, any config,
        both backends agree bit-for-bit."""
        ev = _stream(seed=seed, batch=batch, n=n, ragged=ragged)
        got = _pallas(ev, mode=mode, oob=oob, block_t=block_t)
        want = _jnp(ev, mode=mode, oob=oob)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
