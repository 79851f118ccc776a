"""Seeded sensor content for the chip benchmark: DVS event windows and
Bayer frames.

The four DVS scenario generators are copies of the program's
``repro.data.synthetic`` scenarios (``moving_bar``, ``flicker``,
``noise_burst``, ``crossing``), kept here so that no change to the
program can move the benchmark's traffic.  Each returns one window as a
tuple of ``[n_events]`` arrays ``(t, x, y, p, valid)`` with ``t`` in
``[0, window)``.  Everything is a pure function of a JAX key and is
generated in bulk on the device by :func:`event_pool` and
:func:`bayer_pool`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _finish(t, x, y, p, n_live, *, height, width, window):
    n = t.shape[0]
    return (jnp.clip(t, 0.0, window * (1.0 - 1e-6)).astype(jnp.float32),
            jnp.clip(x.astype(jnp.int32), 0, width - 1),
            jnp.clip(y.astype(jnp.int32), 0, height - 1),
            jnp.clip(p.astype(jnp.int32), 0, 1),
            jnp.arange(n) < n_live)


def moving_bar(rng, *, height, width, n_events, window=1.0, rate=1.0,
               speed=0.6, bar_width=0.08, vertical=True, noise_frac=0.02):
    """Ego-motion sweep: a bar crosses the field of view; ON events at
    its leading edge, OFF at its trailing edge."""
    k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
    t = jax.random.uniform(k1, (n_events,), maxval=window)
    along = jax.random.uniform(k2, (n_events,))
    lead = jax.random.bernoulli(k3, 0.5, (n_events,))
    centre = (0.1 + speed * t / window) % 1.0
    across = centre + jnp.where(lead, bar_width / 2, -bar_width / 2)
    noise = jax.random.bernoulli(k4, noise_frac, (n_events,))
    nx = jax.random.uniform(k5, (n_events, 2))
    across = jnp.where(noise, nx[:, 0], across)
    along = jnp.where(noise, nx[:, 1], along)
    xf = jnp.where(vertical, across, along)
    yf = jnp.where(vertical, along, across)
    return _finish(t, xf * width, yf * height, lead.astype(jnp.int32),
                   int(n_events * rate), height=height, width=width,
                   window=window)


def flicker(rng, *, height, width, n_events, window=1.0, rate=0.12,
            flicker_hz=3.0, source_radius=0.08):
    """Night: one small light source flickers; events cluster at its
    on/off transitions and the window is far under its budget."""
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    centre = jax.random.uniform(k1, (2,), minval=0.25, maxval=0.75)
    n_trans = max(1, int(2 * flicker_hz * window))
    edge = jax.random.randint(k2, (n_events,), 0, n_trans)
    jitter = jax.random.normal(k3, (n_events,)) * (window / n_trans * 0.05)
    t = (edge + 0.5) / n_trans * window + jitter
    offs = jax.random.normal(k4, (n_events, 2)) * source_radius
    return _finish(t, (centre[0] + offs[:, 0]) * width,
                   (centre[1] + offs[:, 1]) * height, edge % 2,
                   int(n_events * rate), height=height, width=width,
                   window=window)


def noise_burst(rng, *, height, width, n_events, window=1.0, rate=1.0,
                burst_frac=0.6, burst_width=0.08, n_streaks=12):
    """Rain: incoherent background noise plus a temporal burst of
    vertical streaks that fills the window."""
    k1, k2, k3, k4, k5, k6 = jax.random.split(rng, 6)
    t_bg = jax.random.uniform(k1, (n_events,), maxval=window)
    burst_t0 = jax.random.uniform(k2, (), maxval=window * (1 - burst_width))
    in_burst = jax.random.bernoulli(k3, burst_frac, (n_events,))
    t = jnp.where(in_burst,
                  burst_t0 + (t_bg / window) * burst_width * window, t_bg)
    streak = jax.random.randint(k4, (n_events,), 0, n_streaks)
    streak_x = jax.random.uniform(k5, (n_streaks,))
    u = jax.random.uniform(k6, (n_events, 3))
    xf = jnp.where(in_burst, streak_x[streak], u[:, 0])
    yf = jnp.where(in_burst, (t - burst_t0) / (burst_width * window),
                   u[:, 1])
    p = (u[:, 2] > 0.5).astype(jnp.int32)
    return _finish(t, xf * width, yf * height, p, int(n_events * rate),
                   height=height, width=width, window=window)


def crossing(rng, *, height, width, n_events, window=1.0, rate=0.8,
             n_objects=3, obj_size=0.12):
    """Several rigid squares enter from the edges and cross near the
    centre; the buffer is padded to ``n_events`` with dead events."""
    ks = jax.random.split(rng, 5)
    per = n_events // n_objects
    n_used = per * n_objects
    side = jax.random.randint(ks[0], (n_objects,), 0, 4)
    lane = jax.random.uniform(ks[1], (n_objects,), minval=0.2, maxval=0.8)
    zero, one = jnp.zeros_like(lane), jnp.ones_like(lane)
    sx = jnp.select([side == 0, side == 1, side == 2, side == 3],
                    [zero, one, lane, lane])
    sy = jnp.select([side == 0, side == 1, side == 2, side == 3],
                    [lane, lane, zero, one])
    vx, vy = 0.5 - sx, 0.5 - sy
    t = jax.random.uniform(ks[2], (n_objects, per), maxval=window)
    u = jax.random.uniform(ks[3], (n_objects, per, 2)) - 0.5
    cx = sx[:, None] + vx[:, None] * 2.0 * t / window
    cy = sy[:, None] + vy[:, None] * 2.0 * t / window
    ex = cx + u[..., 0] * obj_size
    ey = cy + u[..., 1] * obj_size
    lead = (u[..., 0] * vx[:, None] + u[..., 1] * vy[:, None]) > 0
    perm = jax.random.permutation(ks[4], n_used)
    ev = _finish(t.reshape(-1)[perm], ex.reshape(-1)[perm] * width,
                 ey.reshape(-1)[perm] * height, lead.reshape(-1)[perm],
                 int(n_used * rate), height=height, width=width,
                 window=window)
    pad = n_events - n_used
    return tuple(jnp.pad(a, (0, pad)) for a in ev)


SCENARIOS = {"moving_bar": moving_bar, "flicker": flicker,
             "noise_burst": noise_burst, "crossing": crossing}


def event_pool(key, scenes, per_scene: int, *, height: int, width: int,
               n_events: int):
    """``per_scene`` windows of each scene, in one jitted call.

    ``scenes`` is a tuple of ``(scenario name, ((kwarg, value), ...))``.
    Returns ``(t, x, y, p, valid)``, each ``[len(scenes) * per_scene,
    n_events]``, scene-major."""
    def make(k):
        out = []
        for i, (name, kw) in enumerate(scenes):
            keys = jax.random.split(jax.random.fold_in(k, i), per_scene)
            fn = SCENARIOS[name]
            out.append(jax.vmap(lambda kk: fn(
                kk, height=height, width=width, n_events=n_events,
                **dict(kw)))(keys))
        return tuple(jnp.concatenate(parts) for parts in zip(*out))
    return jax.jit(make)(key)


def bayer_pool(key, n: int, height: int, width: int):
    """``n`` RGGB mosaics in [0, 1]: a smooth scene of a few shaded
    rectangles under a colour cast, sensor noise, and hot and dead
    pixels for the defect correction to find.  One jitted call."""
    def one(k):
        ks = jax.random.split(k, 7)
        yy = jnp.linspace(0.0, 1.0, height)[:, None]
        xx = jnp.linspace(0.0, 1.0, width)[None, :]
        img = 0.25 + 0.3 * yy + 0.1 * xx + jnp.zeros((height, width))
        lo = jax.random.uniform(ks[0], (6, 2), maxval=0.7)
        size = jax.random.uniform(ks[1], (6, 2), minval=0.1, maxval=0.3)
        level = jax.random.uniform(ks[2], (6,), minval=0.05, maxval=0.9)
        for j in range(6):
            inside = ((yy >= lo[j, 0]) & (yy < lo[j, 0] + size[j, 0])
                      & (xx >= lo[j, 1]) & (xx < lo[j, 1] + size[j, 1]))
            img = jnp.where(inside, level[j], img)
        cast = jax.random.uniform(ks[3], (2,), minval=0.6, maxval=1.4)
        is_r = (jnp.arange(height)[:, None] % 2 == 0) & \
            (jnp.arange(width)[None, :] % 2 == 0)
        is_b = (jnp.arange(height)[:, None] % 2 == 1) & \
            (jnp.arange(width)[None, :] % 2 == 1)
        mosaic = img * jnp.where(is_r, cast[0], jnp.where(is_b, cast[1], 1.0))
        mosaic = mosaic + 0.02 * jax.random.normal(ks[4], (height, width))
        defect = jax.random.uniform(ks[5], (height, width)) < 0.002
        hot = jax.random.uniform(ks[6], (height, width)) > 0.5
        mosaic = jnp.where(defect, jnp.where(hot, 1.0, 0.0), mosaic)
        return jnp.clip(mosaic, 0.0, 1.0).astype(jnp.float32)
    return jax.jit(jax.vmap(one))(jax.random.split(key, n))
