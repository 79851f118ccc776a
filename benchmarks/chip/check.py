"""Whether the timed path served correct results.

Once the window has closed and the engine is freed, a sample of the
requests the window served, drawn from the seed, is run through the
plain reference (``reference/<name>.py``) with the same weights, event
windows and frames, and the served outputs are compared with it:

``npu_median_gap``
    per request, the widest |served - reference| over its detection
    outputs (voxelizer, backbone, YOLO head) and its control vector
    (control head); the median over the sample.  The median, because a
    spiking layer's threshold turns a rounding difference in a membrane
    potential into a whole spike on a few requests in a hundred, which
    moves that request's outputs by up to about 2; every request of a
    lower-precision run departs at its rounding level;
``npu_unexplained``
    sampled requests that depart (NPU gap above ``departure.gap``, the
    rounding level) although the reference holds no membrane potential
    within ``departure.near_threshold`` of the threshold: a departure
    that no flipped spike explains.  The median lets through a fault
    that hits fewer than half of the requests; this holds each
    departing request to the one cause the median is there for;
``rgb_gap``
    widest gap of the rendered frames, against the reference ISP run
    on the served control vector, so that the ISP is judged on its own
    inputs (and a control vector changed after the ISP read it shows);
``twin_gap``
    widest gap between the served detections, control vector and frame
    of a sampled request and those of up to ``harness.TWINS`` other
    requests of the window that carried the same event window and
    frame: a result may not depend on the slot, the tick or the other
    requests it was served with.  Exact: the program is deterministic;
``undelivered``
    sampled requests admitted in the window and never delivered;
``compared``, ``twins_compared``
    sampled requests compared with the reference, and pairs of twins
    compared, at least ``MIN_COMPARED`` each.

Each limit, and the departure rule, is in ``checks/<config>.json``;
``PERF.md`` gives the readings they were set from.  The run is correct
when every number is within its limit.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SAMPLE = 64                   # requests compared with the reference
MIN_COMPARED = 16
BLOCK_PIXELS = 2 ** 21         # frame pixels per reference call: at
                              # 720p two frames, whose ISP temporaries
                              # take about 3 GB
CLOSED_LOOP_SPAN = 32         # a closed loop samples its first
                              # CLOSED_LOOP_SPAN * SAMPLE requests


def checks(cfg: dict) -> dict:
    """The configuration's limits, ``{"limits": {number: limit},
    "departure": {"gap": ..., "near_threshold": ...}}``."""
    with open(os.path.join(HERE, "checks", cfg["name"] + ".json")) as f:
        return json.load(f)


def draw_sample(seed: int, cell, sched) -> set:
    """The request ids whose outputs are kept for the comparison."""
    rng = np.random.default_rng([seed, 0xC4EC])
    n = len(sched.due) if cell.open_loop else CLOSED_LOOP_SPAN * SAMPLE
    return set(int(i) for i in rng.choice(n, min(SAMPLE, n), replace=False))


@dataclasses.dataclass
class Verdict:
    numbers: dict             # name -> {"value": v, "limit": l}

    @property
    def correct(self) -> bool:
        return all(n["value"] <= n["limit"] if "limit" in n
                   else n["value"] >= n["min"]
                   for n in self.numbers.values())

    def lines(self):
        out = [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
               if "limit" in v else
               f"check {k}: {v['value']!r} (at least {v['min']!r})"
               for k, v in self.numbers.items()]
        return out + [f"check: correct={self.correct}"]


def block(bayer) -> int:
    """Requests per reference call: as many frames as BLOCK_PIXELS hold,
    at most a tick's eight."""
    return int(max(1, min(8, BLOCK_PIXELS // bayer[0].size)))


def reference_outputs(cfg, params, events, bayer, precision="highest"):
    """The reference's (raw_pred, control, rgb, margin), in blocks of
    requests."""
    from harness import reference
    fwd, b = reference(cfg).make_forward(cfg, precision), block(bayer)
    outs = [fwd(params, *[e[i:i + b] for e in events], bayer[i:i + b])
            for i in range(0, len(bayer), b)]
    return tuple(np.concatenate([np.asarray(o[k]) for o in outs])
                 for k in range(4))


def reference_isp(cfg, bayer, control, precision="highest"):
    from harness import reference
    fn, b = reference(cfg).make_isp(precision), block(bayer)
    return np.concatenate([np.asarray(fn(bayer[i:i + b], control[i:i + b]))
                           for i in range(0, len(bayer), b)])


def gaps(cfg, params, events, bayer, raw, control, rgb) -> dict:
    """Per request, the widest gap of the served (raw, control, rgb)
    from the reference's, rgb against the reference ISP on the served
    control; and the reference's membrane-potential margin."""
    ref_raw, ref_ctl, _, margin = reference_outputs(cfg, params, events,
                                                    bayer)
    ref_rgb = reference_isp(cfg, bayer, control)
    widest = lambda a, b: np.max(np.abs(a - b).reshape(len(a), -1), axis=1)
    return {"raw_pred": widest(raw, ref_raw),
            "control": widest(control, ref_ctl),
            "rgb": widest(rgb, ref_rgb), "margin": margin}


def numbers(per_request: dict, departure: dict) -> dict:
    """The numbers compared with the reference, from per-request gaps."""
    npu = np.maximum(per_request["raw_pred"], per_request["control"])
    unexplained = ((npu > departure["gap"])
                   & (per_request["margin"] > departure["near_threshold"]))
    return {"npu_median_gap": float(np.median(npu)),
            "npu_unexplained": int(np.sum(unexplained)),
            "rgb_gap": float(np.max(per_request["rgb"]))}


def twin_gaps(entries: dict, outputs: dict, twins: dict) -> list:
    """Per pair of a sampled request and a twin, their widest gap (NaN
    where either holds a NaN)."""
    return [float(np.max([np.max(np.abs(a - b))
                          for a, b in zip(outputs[r], tw)]))
            for r in sorted(outputs) for tw in twins.get(entries[r], [])]


def compare(cfg, seed, inputs, entries: dict, outputs: dict, twins: dict,
            undelivered: int) -> Verdict:
    """``entries``: sampled rid -> pool entry; ``outputs``: sampled rid ->
    served (raw_pred, control, rgb), for those delivered; ``twins``: pool
    entry -> served outputs of other requests that carried it."""
    from harness import make_weights
    spec = checks(cfg)
    lim = spec["limits"]
    rids = sorted(outputs)
    out = {}
    if rids:
        events, bayer = inputs.payload([entries[r] for r in rids])
        raw, ctl, rgb = (np.stack([outputs[r][k] for r in rids])
                         for k in range(3))
        params = make_weights(cfg, seed)
        found = numbers(gaps(cfg, params, events, bayer, raw, ctl, rgb),
                        spec["departure"])
        out = {k: {"value": v, "limit": lim[k]} for k, v in found.items()}
    pairs = twin_gaps(entries, outputs, twins)
    out["twin_gap"] = {"value": float(np.max(pairs)) if pairs else 0.0,
                       "limit": lim["twin_gap"]}
    out["undelivered"] = {"value": undelivered, "limit": lim["undelivered"]}
    out["compared"] = {"value": len(rids), "min": MIN_COMPARED}
    out["twins_compared"] = {"value": len(pairs), "min": MIN_COMPARED}
    return Verdict(out)
