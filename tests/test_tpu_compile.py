"""Compile-only checks of the serving path's Pallas kernels for a TPU v5e.

Each test lowers one kernel through Mosaic (``interpret=False``) at the
shapes the registered configuration serves — ``SNN_ARCHS["spiking_yolo"]``
(64x64, T=5, base_channels 32, 4 stages) at tick batch 8 with the
``"fused"`` ISP plan — and compiles it for a described ``v5e:2x2``
topology with the TPU compiler installed here.  Nothing runs: these
catch what Mosaic refuses (block shapes, unsupported primitives, VMEM
overflow) before a chip run would.  The topology is described inside
a fixture, so only the worker that runs this file loads the TPU
library, and the tests skip where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ISP_CONFIGS, SNN_ARCHS
from repro.core.backbones import mobilenet_specs, vgg_specs, yolo_specs
from repro.core.layers import _same_pads
from repro.kernels import blocks

B = 8                              # FleetConfig's tick batch
EVENTS = 2048                      # EncodingConfig.event_capacity


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def decide_as_on_a_tpu(monkeypatch):
    """Trace-time platform decisions (compiled kernels, full-f32 dots)
    as a TPU backend would take them.  They are read while tracing, so
    jit caches are cleared on both sides: a trace cached by a CPU test
    must not be compiled here, nor one made here serve a later CPU test
    in the same worker."""
    jax.clear_caches()
    monkeypatch.setattr(blocks, "interpret_mode", lambda: False)
    yield
    jax.clear_caches()


def _compiled_text(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(fn, shapes, sharding):
    assert "tpu_custom_call" in _compiled_text(fn, shapes, sharding)


def _cfg(name="spiking_yolo"):
    return SNN_ARCHS[name]


def _layer(specs, name, cfg):
    """(spec, input H, input W) of a named layer of a linear run."""
    from repro.kernels.backbone_fuse import layer_out_hw
    h, w = cfg.height, cfg.width
    for s in specs:
        if s.name == name:
            return s, h, w
        h, w = layer_out_hw(s, h, w)
    raise KeyError(name)


def test_event_voxel_compiles(one_chip):
    from repro.kernels.event_voxel import event_voxel_pallas
    cfg = _cfg()
    f32, i32 = jnp.float32, jnp.int32
    _assert_kernel(
        lambda t, x, y, p, v: event_voxel_pallas(
            t, x, y, p, v, time_steps=cfg.time_steps, height=cfg.height,
            width=cfg.width, interpret=False),
        [((B, EVENTS), f32)] + [((B, EVENTS), i32)] * 4, one_chip)


@pytest.mark.parametrize("layer", ["d0", "f0", "d1", "f1", "d2", "f2",
                                   "d3", "f3"])
def test_spike_conv_compiles(one_chip, layer):
    from repro.kernels.spike_conv import spike_conv_pallas
    cfg = _cfg()
    s, h, w = _layer(yolo_specs(cfg), layer, cfg)
    _, _, ho = _same_pads(h, s.kernel, s.stride)
    _, _, wo = _same_pads(w, s.kernel, s.stride)
    M, K = B * cfg.time_steps * ho * wo, s.kernel ** 2 * s.cin
    _assert_kernel(
        lambda a, b: spike_conv_pallas(a, b, gated=True, interpret=False),
        [((M, K), jnp.float32), ((K, s.cout), jnp.float32)], one_chip)


@pytest.mark.parametrize("layer", ["d0", "f0", "d1", "f1", "d2", "f2"])
def test_spike_conv_direct_compiles(one_chip, layer):
    """The per-op spiking conv of every layer the shape rule sends down
    the direct path (``ops.direct_conv_rule``), through ``spike_conv_op``
    as the tick runs it: the pad and phase split, then the kernel."""
    from repro.kernels import ops
    cfg = _cfg()
    s, h, w = _layer(yolo_specs(cfg), layer, cfg)
    xs = (B * cfg.time_steps, h, w, s.cin)
    ws = (s.kernel, s.kernel, s.cin, s.cout)
    assert ops.direct_conv_rule(xs, ws, s.stride, False)
    _assert_kernel(lambda x, k: ops.spike_conv_op(x, k, stride=s.stride),
                   [(xs, jnp.float32), (ws, jnp.float32)], one_chip)


@pytest.mark.parametrize("layer", ["d0", "f0", "f3"])
def test_spike_conv_lif_compiles(one_chip, layer):
    """The fused conv→norm→LIF layer kernel (the ``conv_lif`` op's
    ``fused=True`` variant), one program per batch element."""
    from repro.kernels.ops import NORM_EPS
    from repro.kernels.spike_conv import spike_conv_lif_pallas
    cfg = _cfg()
    s, h, w = _layer(yolo_specs(cfg), layer, cfg)
    _, _, ho = _same_pads(h, s.kernel, s.stride)
    _, _, wo = _same_pads(w, s.kernel, s.stride)
    T, HW, K = cfg.time_steps, ho * wo, s.kernel ** 2 * s.cin
    _assert_kernel(
        lambda a, b, sc, bi: spike_conv_lif_pallas(
            a, b, sc, bi, T=T, B=B, HW=HW, tau=cfg.tau_mem,
            v_th=cfg.v_threshold, v_reset=cfg.v_reset, eps=NORM_EPS,
            interpret=False),
        [((B * T * HW, K), jnp.float32), ((K, s.cout), jnp.float32),
         ((s.cout,), jnp.float32), ((s.cout,), jnp.float32)], one_chip)


def test_spike_dwconv_compiles(one_chip):
    from repro.kernels.spike_conv import spike_dwconv_pallas
    cfg = _cfg("spiking_mobilenet")
    s, h, w = _layer(mobilenet_specs(cfg), "dw0", cfg)
    _, _, ho = _same_pads(h, s.kernel, s.stride)
    _, _, wo = _same_pads(w, s.kernel, s.stride)
    taps, M = s.kernel ** 2, B * cfg.time_steps * ho * wo
    _assert_kernel(
        lambda a, b: spike_dwconv_pallas(a, b, gated=True,
                                         interpret=False),
        [((M, taps, s.cin), jnp.float32), ((taps, s.cin), jnp.float32)],
        one_chip)


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_norm_affine_lif_compiles(one_chip, stage):
    from repro.kernels.lif_scan import norm_affine_lif_pallas
    cfg = _cfg()
    s, h, w = _layer(yolo_specs(cfg), f"f{stage}", cfg)
    C = s.cout
    _assert_kernel(
        lambda y, sc, bi: norm_affine_lif_pallas(y, sc, bi,
                                                 interpret=False),
        [((cfg.time_steps, B, h * w, C), jnp.float32),
         ((C,), jnp.float32), ((C,), jnp.float32)], one_chip)


def test_lif_scan_compiles(one_chip):
    from repro.kernels.lif_scan import lif_scan_pallas
    cfg = _cfg()
    # the control head's hidden layer: [T, B * 64] currents
    _assert_kernel(lambda c: lif_scan_pallas(c, interpret=False),
                   [((cfg.time_steps, B * 64), jnp.float32)], one_chip)


def test_spike_matmul_compiles(one_chip):
    from repro.kernels.spike_matmul import spike_matmul_pallas
    cfg = _cfg()
    nd = ISP_CONFIGS["fused"].control_dim
    # the control head's readout: [T * B, 64] spikes @ [64, control_dim]
    _assert_kernel(
        lambda a, b: spike_matmul_pallas(a, b, interpret=False),
        [((cfg.time_steps * B, 64), jnp.float32), ((64, nd), jnp.float32)],
        one_chip)


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_max_pool_compiles(one_chip, stage):
    from repro.kernels.backbone_fuse import max_pool_pallas
    cfg = _cfg("spiking_vgg")
    s, h, w = _layer(vgg_specs(cfg), f"s{stage}_b", cfg)
    _assert_kernel(
        lambda x: max_pool_pallas(x, window=s.pool, gated=True,
                                  interpret=False),
        [((B * cfg.time_steps, h, w, s.cout), jnp.float32)], one_chip)


@pytest.mark.parametrize("isp,stages,tail", [
    ("fused", ("exposure", "dpc"), ()),
    ("fused", ("demosaic",), ()),
    ("fused", ("awb", "nlm"), (3,)),
    ("fused", ("gamma", "sharpen"), (3,)),
    ("fast_preview", ("awb", "gamma"), (3,)),     # a pointwise segment
])
def test_isp_segment_compiles(one_chip, isp, stages, tail):
    """One segment of an ISP fusion plan, batched over the tick the way
    the engine runs it (``jax.vmap``), through the normal fused-ISP
    executor with the kernels compiled."""
    from repro.isp.fuse import plan_stages, run_fused_stages
    from repro.isp.stages import control_dim_for, control_to_stage_params
    assert stages in [seg.stages for seg in
                      plan_stages(ISP_CONFIGS[isp].stages)]
    cfg = _cfg()
    nd = control_dim_for(stages)
    _assert_kernel(
        jax.vmap(lambda r, c: run_fused_stages(
            r, control_to_stage_params(c, stages), stages)),
        [((B, cfg.height, cfg.width) + tail, jnp.float32),
         ((B, nd), jnp.float32)], one_chip)


@pytest.mark.parametrize("stage", ["demosaic", "nlm"])
def test_isp_stage_kernel_compiles(one_chip, stage):
    """The per-stage ``"pallas"`` ISP backend's kernels, on one frame."""
    from repro.kernels.demosaic import demosaic_pallas
    from repro.kernels.nlm import nlm_pallas
    cfg = _cfg()
    hw = (cfg.height, cfg.width)
    if stage == "demosaic":
        _assert_kernel(lambda r: demosaic_pallas(r, interpret=False),
                       [(hw, jnp.float32)], one_chip)
    else:
        _assert_kernel(lambda x, s: nlm_pallas(x, s, interpret=False),
                       [(hw + (3,), jnp.float32), ((), jnp.float32)],
                       one_chip)


def test_registered_config_is_the_compiled_one():
    """The shapes above are the registered configuration's."""
    cfg = _cfg()
    assert (cfg.height, cfg.width, cfg.time_steps, cfg.base_channels,
            cfg.num_stages) == (64, 64, 5, 32, 4)
