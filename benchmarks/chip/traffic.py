"""The one traffic generator: reads a mix from ``traffic/<name>.json``.

A mix is data only:

``loop``
    ``"open"``: ``streams`` sensor heads, each due every ``period_ms``
    at a random phase in ``[0, period_ms)``, and each window late by a
    seeded jitter in ``[0, jitter_ms)``.  Latency runs from the due
    time.  The phases are drawn once from the mix's ``phase_seed`` and
    dealt to the streams in an order drawn from the run's seed: every
    seed sends the same arrivals, as bursty as independent heads, and
    moves only which head (and so which scene) sends at which phase.
    ``"closed"``: ``drives`` clients each keep ``outstanding`` windows
    in the system and send the next one when one is delivered.  Latency
    runs from the submit.
``scenes``
    ``[[scenario, {kwarg: value}], ...]``; stream (or drive) ``s`` sends
    scene ``(s + k) % len(scenes)`` as its window ``k``.
``pool_per_scene``
    How many distinct windows of each scene are made in set-up; a
    request takes one of them, drawn from the seed.

Every request carries one event window and one Bayer frame.  The sizes
and the number of requests are the same for every seed; the seed moves
the phases, the jitter, the event content and which pool entry each
request takes.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TABLE = 512           # pool picks drawn per client, then reused in turn


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"traffic {name}: unknown loop {mix['loop']!r}")
    return mix


def scene_tuple(mix) -> tuple:
    """The mix's scenes as a hashable ``(name, ((kwarg, value), ...))``
    tuple, the form ``scenes.event_pool`` takes."""
    return tuple((s, tuple(sorted(kw.items()))) for s, kw in mix["scenes"])


class Schedule:
    """Which pool entries the requests take, and, in an open loop, when
    each is due.  Request ``i`` of an open loop is the ``i``-th in due
    order; of a closed loop, the ``i``-th submitted."""

    def __init__(self, mix: dict, seed: int, seconds: float):
        self.mix = mix
        self.rng = np.random.default_rng([seed, 0x5CE7E])
        self.n_scenes = len(mix["scenes"])
        self.per_scene = int(mix["pool_per_scene"])
        if mix["loop"] == "open":
            n = int(mix["streams"])
            period = mix["period_ms"] / 1e3
            jitter = mix["jitter_ms"] / 1e3
            phases = np.random.default_rng(
                [int(mix["phase_seed"]), 0xFA5E]).uniform(0.0, period, n)
            phase = phases[self.rng.permutation(n)]
            windows = int(np.ceil(seconds / period)) + 1
            k = np.arange(windows)
            due = (phase[:, None] + k[None, :] * period
                   + self.rng.uniform(0.0, jitter, (n, windows)))
            stream = np.repeat(np.arange(n)[:, None], windows, 1)
            keep = due < seconds
            order = np.argsort(due[keep], kind="stable")
            self.due = due[keep][order]
            self.stream = stream[keep][order]
            self.window = np.broadcast_to(k, due.shape)[keep][order]
            self.n_clients = n
        else:
            self.n_clients = int(mix["drives"])
            self.due = None
        self.pick = self.rng.integers(self.per_scene,
                                      size=(self.n_clients, TABLE))

    def entry(self, client: int, window: int) -> int:
        """Pool index of a client's ``window``-th request."""
        scene = (client + window) % self.n_scenes
        return scene * self.per_scene + int(self.pick[client,
                                                      window % TABLE])
