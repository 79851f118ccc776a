"""Multi-device integration checks, run as a subprocess with 8 host
devices (tests/test_distributed.py wraps this; smoke tests keep 1
device per the dry-run isolation rule)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.configs.registry import reduced
from repro.distributed.sharding import MeshAxes, from_mesh
from repro.models import transformer as tfm
from repro.models.lm import lm_loss, serve_decode
from repro.optim.adamw import AdamWConfig
from repro.train.state import init_train_state
from repro.train.step import make_train_step

AUTO = AxisType.Auto


def _mesh_context(mesh):
    """jax.sharding.set_mesh landed after 0.4.x; on older jax the Mesh
    object itself is the equivalent context manager."""
    set_mesh = getattr(jax.sharding, "set_mesh", None)
    return set_mesh(mesh) if set_mesh is not None else mesh



def check_sharded_loss_matches_local():
    """pjit on a (2 data, 4 model) mesh == single-device math, incl. the
    shard_map MoE and the ZeRO param shardings."""
    mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AUTO, AUTO))
    ax = from_mesh(mesh)
    local = MeshAxes()
    for arch in ("qwen2-7b", "arctic-480b", "jamba-v0.1-52b"):
        cfg = dataclasses.replace(reduced(arch), dtype="float32")
        if cfg.moe is not None:
            # capacity ample so distributed dispatch == local dispatch
            cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, capacity_factor=8.0))
        rng = jax.random.PRNGKey(0)
        params = tfm.init_params(rng, cfg, dtype=jnp.float32)
        batch = {"tokens": jax.random.randint(rng, (4, 32), 0,
                                              cfg.vocab_size),
                 "labels": jax.random.randint(rng, (4, 32), 0,
                                              cfg.vocab_size)}
        l_local, _ = lm_loss(params, cfg, batch, local, remat="none")
        with _mesh_context(mesh):
            l_dist, _ = jax.jit(
                lambda p, b: lm_loss(p, cfg, b, ax, remat="none")
            )(params, batch)
        err = abs(float(l_local) - float(l_dist)) / abs(float(l_local))
        assert err < 2e-3, f"{arch}: sharded loss differs {err}"
        print(f"  sharded-loss {arch}: local={float(l_local):.5f} "
              f"dist={float(l_dist):.5f} ok")


def check_sharded_decode_matches_local():
    """Sequence-sharded flash-decode (shard_map LSE merge) == local."""
    mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AUTO, AUTO))
    ax = from_mesh(mesh)
    local = MeshAxes()
    for arch in ("qwen2-7b", "deepseek-v3-671b"):
        cfg = dataclasses.replace(reduced(arch), dtype="float32")
        rng = jax.random.PRNGKey(1)
        params = tfm.init_params(rng, cfg, dtype=jnp.float32)
        B, CL = 2, 64
        cache = tfm.init_cache(cfg, B, CL, dtype=jnp.float32)
        # place some history in the cache via prefill
        toks = jax.random.randint(rng, (B, 10), 0, cfg.vocab_size)
        from repro.models.lm import serve_prefill
        _, cache = serve_prefill(params, cfg, {"tokens": toks}, local,
                                 cache_len=CL)
        tok = jax.random.randint(rng, (B, 1), 0, cfg.vocab_size)
        lg_local, _ = serve_decode(params, cfg, cache, tok, jnp.int32(10),
                                   local)
        with _mesh_context(mesh):
            lg_dist, _ = jax.jit(
                lambda p, c, t: serve_decode(p, cfg, c, t, jnp.int32(10),
                                             ax))(params, cache, tok)
        err = float(jnp.max(jnp.abs(lg_local - lg_dist)) /
                    (jnp.max(jnp.abs(lg_local)) + 1e-9))
        assert err < 2e-3, f"{arch}: decode differs {err}"
        print(f"  sharded-decode {arch}: rel_err={err:.2e} ok")


def check_sharded_train_step_runs():
    mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AUTO, AUTO))
    ax = from_mesh(mesh)
    cfg = reduced("qwen2-7b")
    opt = AdamWConfig(lr=1e-3)
    rng = jax.random.PRNGKey(0)
    with _mesh_context(mesh):
        state = init_train_state(rng, cfg, opt)
        step = jax.jit(make_train_step(cfg, opt, ax), donate_argnums=(0,))
        batch = {"tokens": jax.random.randint(rng, (8, 32), 0,
                                              cfg.vocab_size),
                 "labels": jax.random.randint(rng, (8, 32), 0,
                                              cfg.vocab_size)}
        losses = []
        for _ in range(3):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    print(f"  sharded-train: losses={losses} ok")


def check_manual_dp_compression_step():
    """int8 error-feedback cross-pod reduction trains the SNN."""
    from repro.configs.registry import reduced_snn
    from repro.core.npu import init_npu
    from repro.core.train import detection_loss
    from repro.data.synthetic import make_scene_batch
    from repro.distributed.compress import make_manual_dp_train_step
    from repro.optim.adamw import adamw_init, adamw_update

    mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AUTO, AUTO))
    ax = MeshAxes(mesh=mesh, dp=("pod", "data"), tp=None)
    cfg = reduced_snn("spiking_yolo")
    opt = AdamWConfig(lr=2e-3)
    params = init_npu(jax.random.PRNGKey(0), cfg)
    opt_state = adamw_init(params, opt)
    ef = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.bfloat16), params)

    def loss_fn(p, scene):
        return detection_loss(p, scene, cfg)

    def update(p, g, o):
        p2, o2, m = adamw_update(p, g, o, opt)
        return p2, o2, m

    step = make_manual_dp_train_step(loss_fn, ax, update)
    jstep = jax.jit(step)
    losses = []
    with _mesh_context(mesh):
        for i in range(6):
            scene = make_scene_batch(jax.random.PRNGKey(i), batch=8,
                                     height=cfg.height, width=cfg.width,
                                     time_steps=cfg.time_steps)
            params, opt_state, ef, m = jstep(params, opt_state, ef, scene)
            losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert min(losses[-2:]) < max(losses[:2]), losses
    print(f"  compressed-dp: losses={[round(l,3) for l in losses]} ok")


def check_detector_dp_matches_local():
    """Data-parallel spiking-YOLO training (sharded batch + replicated
    state over the ("data",) serving mesh) == the single-device step."""
    from repro.configs.registry import TRAIN_CONFIGS
    from repro.train.detector import (init_detector_state, make_data_fn,
                                      make_detector_train_step,
                                      make_train_mesh, replicate_state,
                                      resolve_snn_config)

    tc = dataclasses.replace(TRAIN_CONFIGS["detector_smoke"], batch=8)
    cfg = resolve_snn_config(tc)
    opt = AdamWConfig(lr=tc.lr, weight_decay=tc.weight_decay,
                      grad_clip=tc.grad_clip)
    mesh = make_train_mesh(tc)
    assert mesh is not None and mesh.axis_names == ("data",), mesh
    ax = from_mesh(mesh)

    def run(ax_, ctx):
        state = init_detector_state(jax.random.PRNGKey(tc.seed), cfg, opt)
        step = make_detector_train_step(cfg, opt)
        data = make_data_fn(tc, cfg, ax_)
        with ctx:
            state = replicate_state(state, ax_)
            losses = []
            for s in range(2):
                state, m = step(state, data(s))
                losses.append(float(m["loss"]))
        return state, losses

    class _null:
        def __enter__(self):
            return None

        def __exit__(self, *a):
            return False

    st_l, lo_l = run(MeshAxes(), _null())
    st_d, lo_d = run(ax, _mesh_context(mesh))
    assert np.allclose(lo_l, lo_d, rtol=1e-5), (lo_l, lo_d)
    for a, b in zip(jax.tree_util.tree_leaves(st_l),
                    jax.tree_util.tree_leaves(st_d)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)
    print(f"  detector-dp: {ax.dp_size}-way losses={lo_d} == local ok")


def check_pipeline_parallel():
    from repro.distributed.pipeline_parallel import (bubble_fraction,
                                                     pipeline_forward)
    mesh = jax.make_mesh((4,), ("pipe",), axis_types=(AUTO,))
    rng = np.random.default_rng(0)
    S, M, mb, d = 4, 8, 2, 16
    Ws = jnp.asarray(rng.normal(0, 0.3, (S, d, d)).astype(np.float32))
    params = {"w": Ws}
    x = jnp.asarray(rng.normal(0, 1, (M, mb, d)).astype(np.float32))

    def stage(p, x):
        return jnp.tanh(x @ p["w"])

    y = pipeline_forward(stage, params, x, mesh)
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ Ws[s])
    assert np.allclose(y, ref, atol=1e-5)
    assert abs(bubble_fraction(4, 8) - 3 / 11) < 1e-9
    print("  pipeline-parallel: exact match ok")


if __name__ == "__main__":
    assert len(jax.devices()) == 8, jax.devices()
    check_sharded_loss_matches_local()
    check_sharded_decode_matches_local()
    check_sharded_train_step_runs()
    check_manual_dp_compression_step()
    check_detector_dp_matches_local()
    check_pipeline_parallel()
    print("ALL DISTRIBUTED CHECKS PASSED")
