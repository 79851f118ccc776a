"""One run of one cell: set-up, the measured window, the drain.

The cell is found by name in ``BENCHMARK.json``; its configuration and
its traffic mix are data files under ``configs/`` and ``traffic/``, and
the configuration names its plain reference, ``reference/<name>.py``.
The program under test is ``repro.serve.fleet.FleetEngine``: the generator
calls its ``submit`` and ``step`` from this one thread, as a client
process would.  Nothing here reaches into the engine beyond its public
calls, the request telemetry it returns, and (with ``trace``) wrappers
that put profiler spans around ``step``, ``upload``, ``dispatch`` and
``fetch``.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import math
import os
import time

import numpy as np

import traffic as traffic_mod

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# the window closes; requests sent in it may still be delivered for
# this long before they count as never delivered
DRAIN_S = 60.0
WARM_TICKS = 4           # full ticks run in set-up, twice
FRAME_POOL = 32          # distinct Bayer frames per run
TWINS = 2                # served twins kept per sampled request


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: list
    per_layer: list

    @property
    def open_loop(self) -> bool:
        return self.mix["loop"] == "open"


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    with open(os.path.join(HERE, "configs", w["config"] + ".json")) as f:
        config = json.load(f)
    check_registered(config)
    reports = lambda m: "workloads" not in m or name in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, config=config, mix=traffic_mod.load(w["traffic"]),
                chips=int(w["chips"]), end_to_end=e2e, per_layer=per_layer)


def seed_key(seed: int, stream: int):
    """A JAX key from a seed of any size, also one over 32 bits."""
    import jax
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


# ------------------------------------------------------------------ set-up

def _sizes(cfg: dict) -> dict:
    return dict(in_channels=cfg["in_channels"],
                time_steps=cfg["time_steps"],
                height=cfg["dvs_resolution"][0],
                width=cfg["dvs_resolution"][1],
                base_channels=cfg["base_channels"],
                num_stages=cfg["num_stages"],
                num_classes=cfg["num_classes"], tau_mem=cfg["tau_mem"],
                v_threshold=cfg["v_threshold"], v_reset=cfg["v_reset"])


def reference(cfg: dict):
    """The configuration's plain reference, ``reference/<name>.py``."""
    return importlib.import_module("reference." + cfg["reference"])


def check_registered(cfg: dict) -> None:
    """Refuses a configuration file whose sizes are not those of the
    architecture it names, as the program registers it, or whose
    architecture is not the one its reference implements."""
    from repro.configs.registry import SNN_ARCHS
    arch = SNN_ARCHS[cfg["snn_arch"]]
    ours = _sizes(cfg)
    theirs = {k: getattr(arch, k) for k in ours}
    implements = reference(cfg).IMPLEMENTS
    kind = {k: getattr(arch, k) for k in implements}
    if ours != theirs or kind != implements:
        raise ValueError(f"config {cfg['name']}: {ours}, {kind} is not the "
                         f"registered {cfg['snn_arch']}: {theirs}, or not "
                         f"what reference/{cfg['reference']}.py implements:"
                         f" {implements}")


def snn_config(cfg: dict):
    """The program's SNNConfig and ISPConfig for a configuration."""
    from repro.configs.registry import ISP_CONFIGS, SNN_ARCHS
    from repro.core.npu import configure_for_isp
    controls = reference(cfg).CONTROL_DIM
    arch = dataclasses.replace(SNN_ARCHS[cfg["snn_arch"]], **_sizes(cfg),
                               backend=cfg["npu_backend"])
    isp = ISP_CONFIGS[cfg["isp"]]
    ncfg = configure_for_isp(arch, isp)
    if ncfg.control_dim != controls:
        raise ValueError(f"ISP {cfg['isp']} takes {ncfg.control_dim} "
                         f"controls; the reference maps {controls}")
    return ncfg, isp


def make_weights(cfg: dict, seed: int):
    """The NPU's weights, made on the device in one jitted call."""
    import jax
    init = reference(cfg).init_weights
    return jax.jit(lambda k: init(k, cfg))(seed_key(seed, 1))


def build_engine(cfg: dict, params):
    from repro.configs.base import EncodingConfig, FleetConfig
    from repro.serve.fleet import FleetEngine
    ncfg, isp = snn_config(cfg)
    return FleetEngine(
        params, ncfg, isp,
        fleet_cfg=FleetConfig(batch=cfg["tick_batch"],
                              max_queue=cfg["max_queue"],
                              double_buffer=cfg["double_buffer"]),
        enc_cfg=EncodingConfig(backend=cfg["encoding_backend"],
                               event_capacity=cfg["event_capacity"]),
        frame_hw=tuple(cfg["frame_resolution"]), mesh=None)


class Inputs:
    """The run's event windows and Bayer frames, made on the device in
    bulk and held on the host as the requests' payloads."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        import jax
        import scenes
        h, w = cfg["dvs_resolution"]
        ev = scenes.event_pool(
            seed_key(seed, 2), traffic_mod.scene_tuple(mix),
            int(mix["pool_per_scene"]), height=h, width=w,
            n_events=cfg["event_capacity"])
        self.events = [np.asarray(a) for a in jax.device_get(ev)]
        fh, fw = cfg["frame_resolution"]
        n = min(FRAME_POOL, len(self.events[0]))
        self.bayer = np.asarray(jax.device_get(
            scenes.bayer_pool(seed_key(seed, 3), n, fh, fw)))

    def request(self, rid: int, entry: int):
        from repro.core.encoding import EventStream
        from repro.serve.cognitive_engine import PerceptionRequest
        return PerceptionRequest(
            rid=rid, events=EventStream(*(a[entry] for a in self.events)),
            bayer=self.bayer[entry % len(self.bayer)])

    def payload(self, entries):
        """Stacked events and frames of pool entries, for the reference."""
        idx = np.asarray(entries)
        return ([a[idx] for a in self.events],
                self.bayer[idx % len(self.bayer)])


# ------------------------------------------------------------------ window

@dataclasses.dataclass
class Record:
    rid: int
    entry: int
    t_ref: float                    # due (open loop) or submit time
    t_admit: float = math.nan
    t_deliver: float = math.nan
    status: str = "pending"


def _copy(result):
    return (np.array(result.raw_pred), np.array(result.control),
            np.array(result.rgb))


class Run:
    """Drives the engine through one cell's traffic and keeps, per
    request, its timestamps; for the sampled ones, its outputs; and the
    outputs of up to ``TWINS`` other requests that carried a sampled
    request's pool entry (its event window and frame)."""

    def __init__(self, engine, inputs: Inputs, sched, sample: set,
                 clock=time.perf_counter):
        self.engine, self.inputs, self.sched = engine, inputs, sched
        self.sample, self.clock = sample, clock
        self.records: dict = {}
        self.outputs: dict = {}         # sampled rid -> served outputs
        self.twins: dict = {}           # entry -> other requests' outputs
        self.sampled_entries: set = set()
        self.live: dict = {}
        self.lateness: list = []
        self.longest_step = 0.0
        self.slow_steps = 0

    def submit(self, rid: int, client: int, window: int, t_ref: float):
        from repro.serve.scheduler import RequestStatus
        entry = self.sched.entry(client, window)
        rec = Record(rid=rid, entry=entry, t_ref=t_ref)
        self.records[rid] = rec
        if rid in self.sample:
            self.sampled_entries.add(entry)
        sreq = self.engine.submit(self.inputs.request(rid, entry))
        if sreq.status in (RequestStatus.REJECTED, RequestStatus.FAILED):
            rec.status = sreq.status.value
        else:
            self.live[rid] = (sreq, client, window)
        return rec

    def step(self):
        """One engine step; returns the (client, window) of every request
        that reached a terminal status."""
        from repro.serve.scheduler import RequestStatus
        done = []
        t = self.clock()
        finished = self.engine.step()
        t = self.clock() - t
        self.longest_step = max(self.longest_step, t)
        self.slow_steps += t > 0.05
        for sreq in finished:
            rid = sreq.request.rid
            _, client, window = self.live.pop(rid)
            rec = self.records[rid]
            rec.status = sreq.status.value
            if sreq.status is RequestStatus.DONE:
                tel = sreq.request.result.telemetry
                rec.t_admit, rec.t_deliver = tel.t_admit, tel.t_deliver
                if rid in self.sample:
                    self.outputs[rid] = _copy(sreq.request.result)
                elif rec.entry in self.sampled_entries:
                    kept = self.twins.setdefault(rec.entry, [])
                    if len(kept) < TWINS:
                        kept.append(_copy(sreq.request.result))
            sreq.request.result = None      # frees the tick's fetch
            done.append((client, window))
        return done

    def busy(self) -> bool:
        return bool(self.live)

    # ---------------------------------------------------------- loops
    def open_loop(self, t0: float, seconds: float, poll=None):
        """Submit each request at its due time; step while work is in the
        system; returns when every request due in the window is
        terminal or ``DRAIN_S`` has passed since the window closed.
        ``poll(seconds since t0)`` is called once per turn."""
        due, stream, window = (self.sched.due, self.sched.stream,
                               self.sched.window)
        i, n = 0, len(due)
        while True:
            now = self.clock() - t0
            while i < n and due[i] <= now:
                self.submit(i, int(stream[i]), int(window[i]), t0 + due[i])
                self.lateness.append(now - due[i])
                i += 1
                now = self.clock() - t0
            if poll is not None:
                poll(now)
            if i >= n and not self.busy():
                return
            if now > seconds + DRAIN_S:
                return
            if self.busy():
                self.step()
            else:
                time.sleep(max(0.0, min(due[i] - now, 0.002)))

    def closed_loop(self, t0: float, seconds: float, outstanding: int,
                    poll=None):
        """Each client keeps ``outstanding`` windows in the system and
        sends its next as soon as one is delivered, until the window
        closes; then the requests in the system drain."""
        rid = 0
        nxt = {}
        for c in range(self.sched.n_clients):
            for _ in range(outstanding):
                self.submit(rid, c, nxt.get(c, 0), self.clock())
                nxt[c] = nxt.get(c, 0) + 1
                rid += 1
        while self.busy():
            now = self.clock() - t0
            if poll is not None:
                poll(now)
            if now > seconds + DRAIN_S:
                return
            for c, _ in self.step():
                if self.clock() - t0 < seconds:
                    self.submit(rid, c, nxt[c], self.clock())
                    nxt[c] += 1
                    rid += 1


def warm(engine, inputs: Inputs, sched, ticks: int = WARM_TICKS):
    """Run full ticks of the cell's own shape until none compiles."""
    batch = engine.batch
    for _ in range(2):
        reqs = [engine.submit(inputs.request(-1 - i, sched.entry(
            i % sched.n_clients, i))) for i in range(batch * ticks)]
        engine.drain()
        for s in reqs:
            s.request.result = None


# ------------------------------------------------------------- reductions

def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank (an infinite value stands for
    a request that was never delivered)."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(q * len(v)) - 1)]


def latencies_ms(records) -> list:
    return [1e3 * (r.t_deliver - r.t_ref) if r.status == "done"
            else math.inf for r in records]
