"""Plain reference of the cognitive perception tick, in float32 jax.numpy.

One request is one DVS event window and one RGGB Bayer frame.  The
reference voxelizes the events (binary occupancy, T bins), runs the
Spiking-YOLO backbone (3x3 spiking convs, each followed by a
per-channel instance norm over (T, H, W), an affine and a LIF neuron
with hard reset), the YOLO head (a spiking 3x3 conv and a 1x1 analog
readout, averaged over T), the control head (spatial mean, a spiking
dense layer, a dense readout, sigmoid of the mean over T), and the
seven-stage ISP (exposure, defect correction, Malvar-He-Cutler
demosaic, grey-world white balance, non-local means, gamma, luma
sharpening) steered by the control vector.

It is written from those equations and imports nothing of the program
under test.  Every dot and convolution takes ``precision`` (``"highest"``
for the reference, ``"high"`` for the lower-precision control).  The
weights are made here too (:func:`init_weights`), from the run's seed, in
the layout the program's ``npu_forward`` reads, so that the program is
handed the very weights the reference uses.

Besides its outputs, the forward gives per request the least distance
of any membrane potential from the threshold (``margin``): a rounding
difference can flip a spike only where that distance is of rounding
size.  ``IMPLEMENTS`` names the architecture these equations are, for
the harness to hold a configuration file to; ``conv_layers`` and
``dense_layers`` give the shapes the operation counts are taken from.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# the program's architecture these equations implement
IMPLEMENTS = {"backbone": "yolo", "detect": True}
ANCHORS = 2
DENSE_HIDDEN = 64
# the ISP's NPU-controlled parameters, in control-vector order, with
# the range each sigmoid output is mapped onto
ISP_CONTROLS = (("exposure", "gain", 0.5, 2.0),
                ("dpc", "threshold", 0.05, 0.5),
                ("awb", "enable", 0.0, 1.0),
                ("awb", "bias_r", 0.5, 2.0),
                ("awb", "bias_b", 0.5, 2.0),
                ("nlm", "strength", 0.0, 1.0),
                ("gamma", "gamma", 0.4, 3.0),
                ("sharpen", "amount", 0.0, 1.0))
CONTROL_DIM = len(ISP_CONTROLS)


def channels(cfg):
    return [cfg["base_channels"] * 2 ** i for i in range(cfg["num_stages"])]


class Conv(NamedTuple):
    name: str
    kernel: int
    stride: int
    cin: int
    cout: int
    fires: bool
    groups: int = 1


def conv_layers(cfg):
    """Every conv, in the order it runs; each takes the previous one's
    output ("SAME" padding)."""
    out, cin = [], cfg["in_channels"]
    for i, c in enumerate(channels(cfg)):
        out += [Conv(f"backbone/d{i}", 3, 2, cin, c, True),
                Conv(f"backbone/f{i}", 3, 1, c, c, True)]
        cin = c
    nout = ANCHORS * (5 + cfg["num_classes"])
    out += [Conv("head/conv", 3, 1, cin, cin, True),
            Conv("head/pred", 1, 1, cin, nout, False)]
    return out


def dense_layers(cfg):
    """(name, inputs, outputs) of the control head's dense layers; each
    runs once per time step."""
    c = channels(cfg)[-1]
    return [("ctrl_hidden", c, DENSE_HIDDEN),
            ("ctrl_out", DENSE_HIDDEN, CONTROL_DIM)]


def init_weights(key, cfg):
    """He-normal conv and dense weights, unit norm scales, zero biases."""
    params = {"backbone": {}, "head": {}}
    layers, dense = conv_layers(cfg), dense_layers(cfg)
    keys = jax.random.split(key, len(layers) + len(dense))
    for k, c in zip(keys, layers):
        group, leaf = c.name.split("/")
        std = math.sqrt(2.0 / (c.kernel * c.kernel * c.cin))
        params[group][leaf] = {
            "w": jax.random.normal(k, (c.kernel, c.kernel, c.cin, c.cout),
                                   jnp.float32) * std,
            "scale": jnp.ones((c.cout,), jnp.float32),
            "bias": jnp.zeros((c.cout,), jnp.float32)}
    for k, (name, cin, n) in zip(keys[len(layers):], dense):
        params[name] = {
            "w": jax.random.normal(k, (cin, n), jnp.float32)
            * math.sqrt(2.0 / cin),
            "bias": jnp.zeros((n,), jnp.float32)}
    return params


# ----------------------------------------------------------------- events

def voxelize(t, x, y, p, valid, cfg):
    """One window -> [T, H, W, 2] binary occupancy; events past the
    window's end fall into the last bin."""
    T, H, W = cfg["time_steps"], cfg["dvs_resolution"][0], \
        cfg["dvs_resolution"][1]
    tbin = jnp.clip(jnp.floor(t * T).astype(jnp.int32), 0, T - 1)
    ok = valid & (x >= 0) & (x < W) & (y >= 0) & (y < H) & (p >= 0) & (p < 2)
    grid = jnp.zeros((T, H, W, 2), jnp.float32)
    grid = grid.at[tbin, jnp.clip(y, 0, H - 1), jnp.clip(x, 0, W - 1),
                   jnp.clip(p, 0, 1)].add(ok.astype(jnp.float32))
    return (grid > 0).astype(jnp.float32)


# -------------------------------------------------------------------- NPU

def lif(currents, cfg):
    """Hard-reset LIF over the leading time axis of [T, B, ...] currents;
    returns the spikes and, per request b, the least |u - v_threshold|
    of any neuron at any step."""
    decay = math.exp(-1.0 / cfg["tau_mem"])
    v_th, v_reset = cfg["v_threshold"], cfg["v_reset"]

    def step(u, i_t):
        u = decay * (u - v_reset) + v_reset + i_t
        s = (u >= v_th).astype(jnp.float32)
        return u * (1.0 - s) + v_reset * s, (s, jnp.abs(u - v_th))

    u0 = jnp.full(currents.shape[1:], v_reset, jnp.float32)
    spikes, dist = jax.lax.scan(step, u0, currents)[1]
    axes = (0,) + tuple(range(2, dist.ndim))
    return spikes, jnp.min(dist, axis=axes)


def spiking_conv(p, x, stride, fire, cfg, precision):
    """x: [T, B, H, W, C] -> ([T, B, H', W', C'], margin [B]); a layer
    that does not fire has an infinite margin."""
    T, B = x.shape[:2]
    y = jax.lax.conv_general_dilated(
        x.reshape((T * B,) + x.shape[2:]), p["w"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
    y = y.reshape((T, B) + y.shape[1:])
    mu = jnp.mean(y, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(y - mu), axis=(0, 2, 3), keepdims=True)
    y = (y - mu) / jnp.sqrt(var + 1e-6) * p["scale"] + p["bias"]
    if fire:
        return lif(y, cfg)
    return y, jnp.full((B,), jnp.inf, jnp.float32)


def npu(params, voxels, cfg, precision="highest"):
    """voxels [B, T, H, W, 2] -> (raw_pred [B, h, w, A, 5+nc],
    control [B, CONTROL_DIM], margin [B])."""
    x = jnp.swapaxes(voxels, 0, 1)
    margin = jnp.full((x.shape[1],), jnp.inf, jnp.float32)
    for c in conv_layers(cfg):
        group, leaf = c.name.split("/")
        if group == "head" and leaf == "conv":
            feats = x
        x, m = spiking_conv(params[group][leaf], x, c.stride, c.fires, cfg,
                            precision)
        margin = jnp.minimum(margin, m)
    raw = jnp.mean(x, axis=0)
    B, h, w, _ = raw.shape
    raw = raw.reshape(B, h, w, ANCHORS, 5 + cfg["num_classes"])
    pooled = jnp.mean(feats, axis=(2, 3))                    # [T, B, C]
    dense = lambda p, v: jnp.matmul(v, p["w"], precision=precision) \
        + p["bias"]
    hidden, m = lif(dense(params["ctrl_hidden"], pooled), cfg)
    control = jax.nn.sigmoid(jnp.mean(dense(params["ctrl_out"], hidden), 0))
    return raw, control, jnp.minimum(margin, m)


# -------------------------------------------------------------------- ISP

def _roll(a, dy, dx):
    return jnp.roll(a, (dy, dx), axis=(0, 1))


def _dpc(raw, threshold):
    nb = jnp.stack([_roll(raw, dy, dx) for dy in (-2, 0, 2)
                    for dx in (-2, 0, 2) if (dy, dx) != (0, 0)], -1)
    diff = raw[..., None] - nb
    bad = jnp.all(diff > threshold, -1) | jnp.all(diff < -threshold, -1)
    trimmed = (jnp.sum(nb, -1) - jnp.min(nb, -1) - jnp.max(nb, -1)) / 6.0
    return jnp.where(bad, trimmed, raw)


# Malvar-He-Cutler filters, scaled by 1/8
_G = ((0, 0, -1, 0, 0), (0, 0, 2, 0, 0), (-1, 2, 4, 2, -1),
      (0, 0, 2, 0, 0), (0, 0, -1, 0, 0))
_ROW = ((0, 0, .5, 0, 0), (0, -1, 0, -1, 0), (-1, 4, 5, 4, -1),
        (0, -1, 0, -1, 0), (0, 0, .5, 0, 0))
_COL = tuple(zip(*_ROW))
_DIAG = ((0, 0, -1.5, 0, 0), (0, 2, 0, 2, 0), (-1.5, 0, 6, 0, -1.5),
         (0, 2, 0, 2, 0), (0, 0, -1.5, 0, 0))


def _filter5(padded, taps, h, w):
    acc = jnp.zeros((h, w), jnp.float32)
    for dy in range(5):
        for dx in range(5):
            if taps[dy][dx]:
                acc = acc + (taps[dy][dx] / 8.0) * padded[dy:dy + h,
                                                          dx:dx + w]
    return acc


def _demosaic(raw):
    h, w = raw.shape
    pad = jnp.pad(raw, 2)
    g, row, col, diag = (_filter5(pad, f, h, w)
                         for f in (_G, _ROW, _COL, _DIAG))
    ey = (jnp.arange(h) % 2 == 0)[:, None]
    ex = (jnp.arange(w) % 2 == 0)[None, :]
    is_r, is_g1, is_g2, is_b = ey & ex, ey & ~ex, ~ey & ex, ~ey & ~ex
    green = jnp.where(is_r | is_b, g, raw)
    red = jnp.where(is_r, raw, jnp.where(is_g1, row,
                                         jnp.where(is_g2, col, diag)))
    blue = jnp.where(is_b, raw, jnp.where(is_g2, row,
                                          jnp.where(is_g1, col, diag)))
    return jnp.clip(jnp.stack([red, green, blue], -1), 0.0, 1.0)


def _awb(rgb, enable, bias_r, bias_b):
    lum = jnp.mean(rgb, -1, keepdims=True)
    ok = ((lum > 0.05) & (lum < 0.95)).astype(jnp.float32)
    means = jnp.sum(rgb * ok, (0, 1)) / jnp.maximum(jnp.sum(ok), 1.0)
    grey = jnp.stack([means[1] / jnp.maximum(means[0], 1e-6), 1.0,
                      means[1] / jnp.maximum(means[2], 1e-6)])
    gains = enable * grey + (1.0 - enable)
    gains = gains * jnp.stack([bias_r, 1.0, bias_b])
    return jnp.clip(rgb * gains, 0.0, 1.0)


def _box3(a):
    a = a + _roll(a, 1, 0) + _roll(a, -1, 0)
    a = a + _roll(a, 0, 1) + _roll(a, 0, -1)
    return a / 9.0


def _nlm(rgb, strength):
    """7x7 search, 3x3 patches on the luma, cyclic borders."""
    h = 1e-3 + 0.2 * strength
    lum = jnp.mean(rgb, -1)
    wsum, acc = 0.0, 0.0
    for dy in range(-3, 4):
        for dx in range(-3, 4):
            d2 = _box3(jnp.square(lum - _roll(lum, dy, dx)))
            wgt = jnp.exp(-d2 / (h * h))
            wsum = wsum + wgt
            acc = acc + wgt[..., None] * _roll(rgb, dy, dx)
    return acc / jnp.maximum(wsum[..., None], 1e-9)


def _gamma(rgb, gamma):
    """256-entry LUT of x^(1/gamma), linearly interpolated."""
    n = 256
    lut = jnp.linspace(0.0, 1.0, n) ** (1.0 / jnp.maximum(gamma, 1e-3))
    idx = jnp.clip((rgb * (n - 1)).astype(jnp.int32), 0, n - 1)
    frac = rgb * (n - 1) - idx
    lo, hi = lut[idx], lut[jnp.minimum(idx + 1, n - 1)]
    return lo + frac * (hi - lo)


_YCC = ((0.299, 0.587, 0.114), (-0.168736, -0.331264, 0.5),
        (0.5, -0.418688, -0.081312))
_YCC_INV = np.linalg.inv(np.asarray(_YCC, np.float64)).astype(np.float32)


def _sharpen(rgb, amount, precision):
    """BT.601 YCbCr, luma unsharp mask with a 5-point cross blur."""
    m = jnp.asarray(_YCC, jnp.float32)
    off = jnp.asarray([0.0, 0.5, 0.5], jnp.float32)
    ycc = jnp.einsum("hwc,dc->hwd", rgb, m, precision=precision) + off
    y = ycc[..., 0]
    blur = (y + _roll(y, 1, 0) + _roll(y, -1, 0) + _roll(y, 0, 1)
            + _roll(y, 0, -1)) / 5.0
    y = jnp.clip(y + amount * (y - blur), 0.0, 1.0)
    ycc = jnp.stack([y, ycc[..., 1], ycc[..., 2]], -1) - off
    return jnp.clip(jnp.einsum("hwc,dc->hwd", ycc, jnp.asarray(_YCC_INV),
                               precision=precision), 0.0, 1.0)


def isp(raw, control, precision="highest"):
    """One [H, W] mosaic and its [CONTROL_DIM] control -> RGB [H, W, 3]."""
    c = {f"{s}.{n}": lo + (hi - lo) * control[i]
         for i, (s, n, lo, hi) in enumerate(ISP_CONTROLS)}
    x = jnp.clip(raw * c["exposure.gain"], 0.0, 1.0)
    x = _dpc(x, c["dpc.threshold"])
    x = _demosaic(x)
    x = _awb(x, c["awb.enable"], c["awb.bias_r"], c["awb.bias_b"])
    x = _nlm(x, c["nlm.strength"])
    x = _gamma(x, c["gamma.gamma"])
    return _sharpen(x, c["sharpen.amount"], precision)


def make_forward(cfg, precision="highest"):
    """jitted (params, events [B, N] x5, bayer [B, H, W]) ->
    (raw_pred, control, rgb, margin)."""
    def fwd(params, t, x, y, p, valid, bayer):
        with jax.default_matmul_precision(precision):
            vox = jax.vmap(lambda *e: voxelize(*e, cfg))(t, x, y, p, valid)
            raw, control, margin = npu(params, vox, cfg, precision)
            rgb = jax.vmap(lambda b, c: isp(b, c, precision))(bayer,
                                                              control)
        return raw, control, rgb, margin
    return jax.jit(fwd)


def make_isp(precision="highest"):
    """jitted (bayer [B, H, W], control [B, CONTROL_DIM]) -> rgb."""
    def fwd(bayer, control):
        with jax.default_matmul_precision(precision):
            return jax.vmap(lambda b, c: isp(b, c, precision))(bayer,
                                                               control)
    return jax.jit(fwd)
