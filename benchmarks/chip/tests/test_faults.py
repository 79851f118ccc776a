"""A run whose timed path is broken underneath must come out not
correct.  Drives the whole of ``run.run`` (set-up, window, drain,
reference comparison) on the CPU at a small size, with the chip check
skipped, and plants one fault, each in the engine core's ``fetch`` or
in the NPU forward inside the tick:

* an answer altered where it is produced: every slot's detections; one
  slot's detections, control vector or frame; one slot's control vector
  before the ISP reads it (so the frame renders from it consistently);
* half of the batch left out: the second half of the slots gets the
  first half's results;
* a step that returns its state unchanged: a tick hands back the
  previous tick's results.

    python3 -m pytest benchmarks/chip/tests/test_faults.py
"""
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(CHIP)),
                                "src"))

import harness  # noqa: E402
import run as bench  # noqa: E402
import traffic  # noqa: E402


def small_cell(traffic_name="replay"):
    """The yolo64 configuration and limits at a size a CPU holds, under
    a closed loop that keeps every tick full."""
    with open(os.path.join(CHIP, "configs", "yolo64.json")) as f:
        cfg = json.load(f)
    cfg.update(dvs_resolution=[32, 32], time_steps=3, base_channels=8,
               frame_resolution=[32, 48], npu_backend="jnp",
               encoding_backend="jnp", isp="default", tick_batch=4)
    mix = traffic.load(traffic_name)
    mix.update(pool_per_scene=8)
    if mix["loop"] == "open":
        mix["streams"] = 12
    e2e = [{"name": n, "unit": u} for n, u in
           (("latency_p50_ms", "ms"), ("latency_p95_ms", "ms"),
            ("delivered_rps", "req/s"), ("setup_s", "s"))]
    return harness.Cell(name="small", config=cfg, mix=mix, chips=1,
                        end_to_end=e2e, per_layer=[])


def run_cell(cell, seed=2 ** 31 + 77):
    import jax
    args = types.SimpleNamespace(seed=seed, seconds=1.5, trace=0)
    return bench.run(args, cell, jax.devices(), log=lambda *a, **k: None)


def _alter(field, slots):
    """Adds 1e-3 to one output value of each of ``slots``."""
    def fault(out, prev):
        o, rgb, sp = out
        arr = np.array(rgb if field == "rgb" else getattr(o, field))
        for s in slots:
            arr[s].flat[0] += 1e-3
        if field == "rgb":
            return o, arr, sp
        return o._replace(**{field: arr}), rgb, sp
    return fault


def _half(out, prev):
    o, rgb, sp = out
    half = rgb.shape[0] // 2
    dup = lambda a: np.concatenate([a[:half], a[:half]])
    o = o._replace(raw_pred=dup(np.asarray(o.raw_pred)),
                   control=dup(np.asarray(o.control)))
    return o, dup(rgb), sp


def _stale(out, prev):
    return prev if prev is not None else out


FETCH_FAULTS = {"raw_pred_altered": _alter("raw_pred", range(4)),
                "raw_pred_one_slot": _alter("raw_pred", [0]),
                "control_altered": _alter("control", [0]),
                "rgb_altered": _alter("rgb", [0]),
                "half_batch": _half,
                "state_unchanged": _stale}


def _plant_in_fetch(name, monkeypatch):
    from repro.serve.engine_core import EngineCore
    real = EngineCore.fetch
    last = {}

    def fetch(self, outputs):
        out = real(self, outputs)
        got = FETCH_FAULTS[name](out, last.get("out"))
        last["out"] = out
        return got
    monkeypatch.setattr(EngineCore, "fetch", fetch)


def _control_before_isp(monkeypatch):
    """Slot 0's control vector moves inside the tick, before the ISP."""
    from repro.serve import engine_core
    real = engine_core.npu_forward

    def npu_forward(*a, **k):
        out = real(*a, **k)
        return out._replace(control=out.control.at[0].add(1e-3))
    monkeypatch.setattr(engine_core, "npu_forward", npu_forward)


FAULTS = dict({n: (lambda mp, n=n: _plant_in_fetch(n, mp))
               for n in FETCH_FAULTS},
              control_one_slot_before_isp=_control_before_isp)


@pytest.fixture(scope="module")
def cell():
    return small_cell()


def test_sound_run_is_correct(cell):
    res = run_cell(cell)
    assert res["correct"], res["checked"]
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_fault_is_caught(cell, name, monkeypatch):
    FAULTS[name](monkeypatch)
    res = run_cell(cell)
    assert not res["correct"], res["checked"]
