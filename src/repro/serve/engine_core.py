"""EngineCore: the device-side half of the cognitive serving stack.

The engine-core/transport split (ROADMAP "fleet serving"):

* ``EngineCore`` (this module) owns everything that touches devices —
  config validation, the ONE jit-cached ``encode -> npu_forward ->
  control -> ISP`` tick executable, the upload/dispatch/fetch
  discipline, and (new) sharding the tick batch across a device mesh.
* ``repro.serve.transport`` owns the host side — numpy staging banks a
  submit memcpys into, double-buffered so tick N+1's upload overlaps
  tick N's compute.  A tick uploads only what its slots use: one
  ``int32`` slab of event windows, frames and flags, and the voxel slab
  only when a slot staged voxels (a zero buffer kept on the device
  stands in for it otherwise).
* ``repro.serve.scheduler`` owns request lifecycle — admission
  control, deadlines, telemetry.
* ``repro.serve.fleet`` composes the three into the multi-device
  continuous-batching ``FleetEngine``; ``repro.serve.cognitive_engine``
  composes core + a single staging bank into the original slot API.

Sharding: pass a 1-D ``("data",)`` mesh (see
``repro.launch.mesh.make_serving_mesh``) and the core replicates the
NPU params once at construction and uploads the slab and the voxel
slab with their batch dimension partitioned over the data axis
(``repro.distributed.sharding.batch_sharding``).  The tick math is
batch-parallel (per-slot instance norms, vmapped ISP), so the tick body
runs once per shard under ``shard_map`` with no resharding; only the
optional per-layer firing rates cross devices (an all-reduce).
``mesh=None`` degrades to the single-device path, bit-for-bit the
pre-split engine.

Tracing: ``upload`` and ``dispatch`` open the ``serve.upload`` and
``serve.dispatch`` profiler spans, and a caller opens ``fetch_span``,
``serve.fetch``, around ``fetch``: each carries the caller's ``tick``,
the transfers the ``bytes`` they move (``serve.upload`` also
``voxels``, 1 when the voxel slab went up).  The tick body's operations
carry the named scopes ``encode``, ``npu``, ``control`` and ``isp`` in
their ``op_name`` metadata.  Both land in a ``jax.profiler`` trace on one
clock; with no profiler running a span costs its enabled-check.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from repro.configs.base import EncodingConfig, ISPConfig, SNNConfig
from repro.core.encoding import events_to_voxel_batch
from repro.core.npu import NPUOutput, npu_forward
from repro.distributed.sharding import (MeshAxes, batch_sharding,
                                        from_mesh, replicated_sharding)
from repro.isp.pipeline import (control_vector_pipeline,
                                legacy_control_permutation)
from repro.isp.stages import BACKENDS as ISP_BACKENDS
from repro.isp.stages import control_to_stage_params
from repro.kernels import tune
from repro.kernels.blocks import matmul_precision
from repro.serve.transport import SlabLayout, bank_nbytes, voxel_shape


def _precision_scope():
    """One matmul precision for the whole tick, whichever backend: the
    kernels ask for the same one (``blocks.matmul_precision``), so every
    rung of the fleet's fallback ladder computes the same numbers on a
    TPU, whose XLA default is one bf16 pass.  Entered at trace time."""
    prec = matmul_precision()
    return (contextlib.nullcontext() if prec is None
            else jax.default_matmul_precision(prec))


class EngineCore:
    """Owns the jit-cached tick executable and its device placement."""

    def __init__(self, npu_params, cfg: SNNConfig,
                 isp_cfg: Optional[ISPConfig] = None, *, batch: int = 4,
                 frame_hw: Optional[tuple] = None,
                 control_order: str = "pipeline",
                 enc_cfg: Optional[EncodingConfig] = None,
                 collect_sparsity: bool = False,
                 mesh=None, tune_table="active"):
        self.cfg = cfg
        self.isp_cfg = isp_cfg if isp_cfg is not None else ISPConfig()
        self.enc_cfg = enc_cfg if enc_cfg is not None else EncodingConfig()
        need = self.isp_cfg.control_dim
        if cfg.control_dim < need:
            raise ValueError(
                f"NPU control_dim={cfg.control_dim} < {need} needed by ISP "
                f"pipeline {self.isp_cfg.name!r}; build the SNNConfig with "
                f"repro.core.npu.configure_for_isp")
        if self.enc_cfg.backend not in ("jnp", "pallas"):
            raise ValueError(f"unknown encoding backend "
                             f"{self.enc_cfg.backend!r}")
        # fail fast at construction rather than at the first tick trace
        if self.isp_cfg.backend not in ISP_BACKENDS:
            raise ValueError(
                f"unknown ISP backend {self.isp_cfg.backend!r}; "
                f"registered: {ISP_BACKENDS}")
        self.batch = batch
        self.frame_hw: Tuple[int, int] = (
            frame_hw if frame_hw is not None else (cfg.height, cfg.width))
        self.layout = SlabLayout(cfg, self.frame_hw,
                                 self.enc_cfg.event_capacity)
        # bytes each transfer moves, for the spans: an upload's without
        # and with the voxel slab now; the tick's outputs once the first
        # dispatch has their shapes
        self.upload_bytes = bank_nbytes(cfg, batch, self.frame_hw,
                                        self.enc_cfg.event_capacity)
        self.fetch_bytes: Optional[int] = None

        if control_order not in ("pipeline", "legacy"):
            raise ValueError(f"control_order must be 'pipeline' or "
                             f"'legacy', got {control_order!r}")
        perm = None
        if control_order == "legacy":
            p = legacy_control_permutation(self.isp_cfg.stages)
            # the permutation gathers *legacy* slot positions, which may
            # exceed the pipeline's derived width (a subset pipeline
            # still reads the historical 8-slot layout) — an undersized
            # head would silently clamp the gather otherwise
            if cfg.control_dim <= max(p):
                raise ValueError(
                    f"NPU control_dim={cfg.control_dim} too narrow for "
                    f"the legacy slot layout (needs > {max(p)})")
            perm = jnp.asarray(p, jnp.int32)

        # ---- mesh placement --------------------------------------------
        self.ax: MeshAxes = from_mesh(mesh)
        self.n_devices = self.ax.dp_size
        if self.n_devices > 1 and batch % self.n_devices:
            raise ValueError(
                f"tick batch={batch} not divisible by the mesh's "
                f"{self.n_devices} data-parallel devices")
        self.params = npu_params
        if self.ax.mesh is not None:
            # params replicated once at construction
            rep = replicated_sharding(self.ax)
            self.params = jax.device_put(npu_params, jax.tree_util.tree_map(
                lambda _: rep, npu_params))
        # the slab [B, words] and the voxel slab [T, B, H, W, C] are
        # partitioned over the data axis on their batch dims (None
        # without a mesh); indexed by whether the voxel slab goes up
        slab_sh = batch_sharding(self.ax, 0)
        vox_sh = batch_sharding(self.ax, 1)
        self._upload_shardings = ((None, None) if slab_sh is None
                                  else ((slab_sh,), (slab_sh, vox_sh)))
        # what the tick reads as voxels when no slot staged any: made
        # once, kept on the device, never donated
        self._zero_voxels = jax.device_put(
            np.zeros(voxel_shape(cfg, batch), np.float32), vox_sh)

        icfg, ncfg, ecfg, nd = self.isp_cfg, cfg, self.enc_cfg, need
        layout = self.layout
        collect = bool(collect_sparsity)

        # Tune-table hoist (ISSUE 9 satellite): snapshot the active
        # table ONCE at construction.  The tick body below resolves
        # every kernel launch config through this snapshot (the
        # ``tune.pinned`` wrapper runs at trace time only), so the
        # per-tick path never re-reads module state / re-stats table
        # files, and a mid-serving ``set_table`` swap cannot half-apply
        # to an engine whose executable is already traced.
        #
        # ``tune_table`` overrides the snapshot: the fleet's fallback
        # ladder builds its "per-layer pallas" rung by pinning an
        # explicitly EMPTY TuningTable (every op resolves to its untuned
        # default, fused=False) — ``pinned(None)`` would be a no-op that
        # falls through to the env/packaged chain at trace time, so the
        # empty table must be passed, not None.
        self._tune_table = (tune.active_table() if tune_table == "active"
                            else tune_table)

        def _encode(events):
            if ecfg.backend == "pallas":
                from repro.kernels.ops import event_voxel_op
                vox = event_voxel_op(
                    events, time_steps=ncfg.time_steps, height=ncfg.height,
                    width=ncfg.width, window=ecfg.window, mode=ecfg.mode,
                    oob=ecfg.oob)
            else:
                vox = events_to_voxel_batch(
                    events, time_steps=ncfg.time_steps, height=ncfg.height,
                    width=ncfg.width, window=ecfg.window, mode=ecfg.mode,
                    oob=ecfg.oob)
            return jnp.moveaxis(vox, 0, 1)            # -> [T, B, H, W, 2]

        def _step(params, slab, voxels):
            # body runs at TRACE time only; ``pinned`` makes every op
            # dispatch inside resolve against the construction-time
            # table snapshot (zero per-tick resolution cost)
            with tune.pinned(self._tune_table), _precision_scope():
                bayer, events, from_events = layout.unpack(slab)
                # encode stage: voxelize the event slots inside the same
                # executable (slots submitted as voxels keep their
                # buffer); traced out entirely for non-DVS layouts
                if ncfg.in_channels == 2:
                    with jax.named_scope("encode"):
                        enc = _encode(events)
                        voxels = jnp.where(
                            from_events[None, :, None, None, None], enc,
                            voxels)
                with jax.named_scope("npu"):
                    out = npu_forward(params, voxels, ncfg,
                                      collect_sparsity=collect)
                # the batch-level sparsity scalars serve training only:
                # dropped here, so the tick neither reduces nor fetches
                # them
                out = out._replace(sparsity=None, tile_skip=None)
                with jax.named_scope("control"):
                    ctrl = out.control[:, perm] if perm is not None \
                        else out.control[:, :nd]
                with jax.named_scope("isp"):
                    rgb = jax.vmap(
                        lambda r, c: control_vector_pipeline(r, c, icfg))(
                            bayer, ctrl)
                    sp = jax.vmap(
                        lambda c: control_to_stage_params(c, icfg.stages))(
                            ctrl)
                return out, rgb, sp

        # one executable serves every tick / control setting / ingestion
        # mix / mesh extent (the FPGA runtime-reconfigurability
        # analogue).  The slab is donated: the per-tick upload hands its
        # device buffer to XLA for reuse.  The voxels are not: they may
        # be the zero buffer, which serves every tick.  (On backends
        # without donation support this is a no-op warning, never an
        # error.)
        step = _step if self.ax.mesh is None else self._per_shard(_step)
        self._step = jax.jit(step, donate_argnums=(1,))

    def _per_shard(self, step):
        """Run the tick body once per data shard (``shard_map``): XLA's
        SPMD partitioner cannot split a Mosaic kernel, and the tick is
        batch-parallel anyway — each device serves its own slots, and
        only the optional per-layer firing rates cross devices, as a
        mean of the per-shard values."""
        d = self.ax.dp_spec
        shard_b = P(d)
        out_specs = (NPUOutput(raw_pred=shard_b, control=shard_b,
                               sparsity=None, tile_skip=None,
                               layer_rates=P()),
                     shard_b, shard_b)

        def body(params, slab, voxels):
            out, rgb, sp = step(params, slab, voxels)
            rates = out.layer_rates
            if rates is not None:
                mean = functools.partial(jax.lax.pmean, axis_name=d)
                out = out._replace(
                    layer_rates={k: mean(v) for k, v in rates.items()})
            return out, rgb, sp

        return jax.shard_map(body, mesh=self.ax.mesh,
                             in_specs=(P(), shard_b, P(None, d)),
                             out_specs=out_specs, check_vma=False)

    # ------------------------------------------------------------------
    # ``tick`` is the caller's number for the tick, carried by the span
    def upload(self, bank, tick: int = -1):
        """ONE host->device transfer of what a staging bank's slots use:
        its slab, and its voxel slab when a slot staged voxels;
        partitioned over the mesh's data axis when sharded.  Returns
        the device ``(slab, voxels)`` for :meth:`dispatch`, with the
        zero buffer as ``voxels`` when they stayed on the host."""
        slots = bank.as_tuple()
        staged = len(slots) > 1
        with TraceAnnotation("serve.upload", tick=tick,
                             bytes=self.upload_bytes[staged],
                             voxels=int(staged)):
            dev = jax.device_put(slots, self._upload_shardings[staged])
        return dev if staged else (dev[0], self._zero_voxels)

    def dispatch(self, slots_dev, tick: int = -1):
        """Launch the tick executable on uploaded slot buffers.  JAX
        dispatch is asynchronous: this returns futures immediately, so a
        caller may upload the NEXT bank while this tick computes (the
        double-buffer overlap ``repro.serve.fleet`` exploits)."""
        slab, voxels = slots_dev
        with TraceAnnotation("serve.dispatch", tick=tick):
            outputs = self._step(self.params, slab, voxels)
        if self.fetch_bytes is None:        # the outputs' shapes, once
            self.fetch_bytes = sum(
                x.nbytes for x in jax.tree_util.tree_leaves(outputs))
        return outputs

    def fetch(self, outputs):
        """ONE batched device->host gather of the tick's output pytree
        (blocks until the compute lands).  Its span is the caller's
        (:meth:`fetch_span`): ``fetch`` keeps the one argument that
        wrappers of it, such as the fault injector's, pass on."""
        return jax.device_get(outputs)

    def fetch_span(self, tick: int = -1):
        """The ``serve.fetch`` span a caller opens around :meth:`fetch`
        of tick ``tick``."""
        return TraceAnnotation("serve.fetch", tick=tick,
                               bytes=self.fetch_bytes or 0)

    def tick(self, bank, tick: int = -1):
        """upload -> dispatch -> fetch of a staging bank in one call
        (the unpipelined path ``CognitiveEngine`` uses)."""
        outputs = self.dispatch(self.upload(bank, tick), tick)
        with self.fetch_span(tick):
            return self.fetch(outputs)
