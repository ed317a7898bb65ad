"""Shared session bootstrap — ONE place a runnable session is built.

``launch.train`` (the classic CLI driver), ``launch.elastic`` (the
rank-failure drill harness) and the tests all need the same sequence:
resolve the arch config, build the mesh/recipe, compile the step
function, init params + optimizer state, wire the data pipeline.  Before
the elastic runtime existed that lived inline in ``launch.train.main``;
the elastic controller has to rebuild a session MID-RUN at a different
world size (over a device SUBSET — the survivors of a shrink, the
enlarged set of a grow), so the bootstrap is factored out here and both
entry points ride it.

``launch.serve`` rides the same config/device/mesh resolution through
:func:`build_serve_session`, which assembles the inference stack
instead: a :class:`repro.serve.ReplicaSet` of engines (optionally on an
expert-parallel mesh for MoE decode) with the initial weights fanned out
over the ``kind="broadcast"`` plan.

The restore path is world-aware: :func:`restore_session` reads any
checkpoint and, when it was written at a different data-parallel world,
remaps the optimizer state through
:func:`repro.optim.zero1.resize_zero1_state` (m/v slice + re-pad, EF
mass conservation) before placing it on the session's mesh.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs import get_config
from repro.data import for_model
from repro.launch import mesh as meshlib
from repro.models import ShardingRecipe, build
from repro.optim.adamw import AdamWConfig
from repro.optim.zero1 import GradSyncConfig, resize_zero1_state
from repro.train import build as build_step


@dataclass
class Session:
    """Everything a training loop needs, bundled.

    ``params``/``opt`` are the LIVE state — :func:`run_step` advances
    them in place.  ``world`` is the data-parallel world this session
    was built for (the dp mesh extent; 1 in single mode).
    """

    cfg: Any
    mode: str
    mesh: Any
    recipe: Any
    model: Any
    opt_cfg: AdamWConfig
    sync: GradSyncConfig
    built: Any
    pipe: Any
    world: int
    params: Any = None
    opt: Any = None

    def use_mesh(self):
        from repro import compat
        return compat.use_mesh(self.mesh) if self.mesh is not None \
            else _null_ctx()


class _null_ctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def resolve_cfg(arch: str, *, scale_down: bool = False,
                moe_dispatch: str | None = None,
                n_layers: int | None = None):
    """Arch-name → config, with the scale-down, MoE-dispatch and depth
    knobs every entry point exposes resolved identically.  ``n_layers``
    cuts depth only (every width stays as published): how a model that
    does not fit whole is sized for one chip."""
    cfg = get_config(arch)
    if scale_down:
        cfg = cfg.scaled_down()
    if n_layers is not None:
        import dataclasses as _dc
        if not 1 <= n_layers <= cfg.n_layers:
            raise ValueError(f"n_layers={n_layers} outside 1..{cfg.n_layers}")
        cfg = _dc.replace(cfg, n_layers=n_layers)
    if moe_dispatch is not None:
        if not cfg.is_moe:
            raise ValueError(
                f"moe_dispatch given but {arch} is not a MoE arch")
        import dataclasses as _dc
        cfg = _dc.replace(cfg, moe_dispatch=moe_dispatch)
    return cfg


def require_devices(n: int, what: str):
    """First ``n`` runtime devices, with the XLA_FLAGS hint every
    launcher prints when the host platform is under-provisioned."""
    if n > jax.device_count():
        raise RuntimeError(
            f"{what} needs {n} devices, have {jax.device_count()} "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count={n})")
    return jax.devices()[:n]


def build_session(*, arch: str, scale_down: bool = False, steps: int = 100,
                  seq_len: int = 128, global_batch: int = 8,
                  dp: int = 1, mp: int = 1, mode: str | None = None,
                  grad_sync: str = "circulant", schedule: str = "halving",
                  wire_dtype: str | None = None, error_feedback: bool = True,
                  use_fused_kernel: bool | None = None,
                  bucket_bytes: int | None = None,
                  moe_dispatch: str | None = None,
                  n_layers: int | None = None,
                  lr: float = 3e-4, warmup: int = 20,
                  compress: str | None = None,
                  devices=None, seed: int = 0,
                  init_state: bool = True) -> Session:
    """Build a runnable :class:`Session` for a ``dp × mp`` mesh.

    ``devices`` may be an explicit device subset (default: the first
    ``dp*mp`` of the runtime's) — the elastic harness passes the
    surviving set when rebuilding at p′ < device_count.  With
    ``init_state=False`` params/opt stay ``None`` (for callers about to
    restore them from a checkpoint anyway).
    """
    cfg = resolve_cfg(arch, scale_down=scale_down,
                      moe_dispatch=moe_dispatch, n_layers=n_layers)
    mode = mode or ("single" if dp * mp == 1 else "zero1")
    opt_cfg = AdamWConfig(lr=lr, warmup_steps=warmup, total_steps=steps)
    pipe = for_model(cfg, seq_len=seq_len, global_batch=global_batch)

    mesh = recipe = None
    if mode != "single":
        if devices is None:
            devices = require_devices(dp * mp, f"mesh {dp}x{mp}")
        elif len(devices) != dp * mp:
            raise ValueError(
                f"mesh {dp}x{mp} needs {dp * mp} devices, got "
                f"{len(devices)}")
        mesh = meshlib.make_mesh((dp, mp), ("data", "model"),
                                 devices=devices)
        recipe = ShardingRecipe(data_axes=("data",), model_axis="model")
    model = build(cfg, recipe=recipe)
    sync = GradSyncConfig(impl=grad_sync, schedule=schedule,
                          wire_dtype=wire_dtype,
                          compress=compress,  # deprecated alias; warns
                          error_feedback=error_feedback,
                          use_fused_kernel=use_fused_kernel,
                          bucket_bytes=bucket_bytes)
    built = build_step(mode, model, opt_cfg, mesh=mesh, recipe=recipe,
                       sync=sync)
    sess = Session(cfg=cfg, mode=mode, mesh=mesh, recipe=recipe, model=model,
                   opt_cfg=opt_cfg, sync=sync, built=built, pipe=pipe,
                   world=dp if mode != "single" else 1)
    if init_state:
        # Params and optimizer state start where the step returns them,
        # so step 1 runs step 0's compiled program.  Each is built by one
        # jitted program rather than op by op.
        key = jax.random.PRNGKey(seed)
        shapes = jax.eval_shape(model.init, key)
        sess.params = jax.jit(model.init, out_shardings=built.param_sharding(
            shapes))(key)
        sess.opt = jax.jit(built.init_opt, out_shardings=built.opt_spec(
            shapes))(sess.params)
    return sess


@dataclass
class ServeSession:
    """The serving counterpart of :class:`Session`: config + engines.

    ``replica_set`` holds ``replicas`` data-parallel engines whose
    weights were fanned out via the broadcast plan (``push_stats``
    records leaf count / payload bytes / rounds); ``ep_mesh`` is the
    expert-parallel mesh MoE decode runs on (None otherwise).
    """

    cfg: Any
    model: Any
    params: Any
    replica_set: Any
    ep_mesh: Any
    push_stats: dict

    @property
    def engine(self):
        """Engine 0 — the one-replica view (scheduler benches use it)."""
        return self.replica_set.engines[0]


def build_serve_session(*, arch: str, max_len: int,
                        scale_down: bool = False,
                        temperature: float = 0.0,
                        moe_dispatch: str | None = None,
                        ep_devices: int = 2, replicas: int = 1,
                        broadcast_schedule: str = "power2",
                        seed: int = 0) -> ServeSession:
    """Build the serving stack with the SAME config/device resolution as
    :func:`build_session` — arch aliasing, scale-down, MoE dispatch
    override, device-count validation with the XLA_FLAGS hint.

    Weights are initialized once and pushed to every replica through the
    ``kind="broadcast"`` plan (bitwise-verified fan-out); with
    ``moe_dispatch="ep"`` each engine decodes inside a shard_map over the
    expert-parallel mesh, exchanging dispatch buffers via the circulant
    alltoall plan.
    """
    from repro.serve import ReplicaSet
    cfg = resolve_cfg(arch, scale_down=scale_down,
                      moe_dispatch=moe_dispatch)
    ep_mesh = None
    if moe_dispatch == "ep":
        devs = require_devices(ep_devices, f"--moe-dispatch ep x{ep_devices}")
        ep_mesh = meshlib.make_mesh((ep_devices,), (cfg.ep_axis,),
                                    devices=devs)
    if replicas > 1:
        require_devices(replicas, f"{replicas} serving replicas")
    model = build(cfg, recipe=None, remat=False)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    rs = ReplicaSet(model, max_len, replicas, temperature=temperature,
                    schedule=broadcast_schedule, engine_mesh=ep_mesh)
    stats = rs.push_weights(params)
    return ServeSession(cfg=cfg, model=model, params=params,
                        replica_set=rs, ep_mesh=ep_mesh, push_stats=stats)


def place_batch(sess: Session, batch: dict) -> dict:
    if sess.mesh is None:
        where = jax.devices()[0]
    else:
        where = NamedSharding(sess.mesh, sess.built.batch_spec)
    return {k: jax.device_put(np.asarray(v), where)
            for k, v in batch.items()}


def run_step(sess: Session, step: int) -> dict:
    """One optimizer step at ``step``'s data-cursor batch; advances
    ``sess.params``/``sess.opt`` in place and returns the metrics."""
    batch = place_batch(sess, sess.pipe.batch_at(step))
    sess.params, sess.opt, metrics = sess.built.step_fn(
        sess.params, sess.opt, batch)
    return metrics


def opt_flat(sess: Session) -> dict:
    """Checkpoint form of the optimizer state: gathered host arrays,
    keyed ``leaf_<i>`` in tree-flatten order (the layout
    :func:`restore_session` and ``launch.train`` both use)."""
    return {f"leaf_{i}": np.asarray(l)
            for i, l in enumerate(jax.tree.leaves(sess.opt))}


def restore_session(sess: Session, mgr, step: int | None = None
                    ) -> tuple[int, dict]:
    """Restore ``mgr``'s checkpoint into ``sess``, resizing across
    world-size changes; returns ``(resumed_step, manifest)``.

    The checkpoint's optimizer leaves are GLOBAL (gathered) arrays, so a
    world mismatch is handled entirely on host: unflatten into the
    saved-world :class:`Zero1State` (its treedef does not depend on
    world — only the EF presence, which ``sess.sync`` determines), run
    ``resize_zero1_state`` to ``sess.world``, then place on the mesh.
    """
    s, params, opt_arrs, man = mgr.restore(step, sess.params)
    sess.params = jax.device_put(params, sess.built.param_sharding(params))
    n = sum(1 for k in opt_arrs if k.startswith("leaf_"))
    treedef = jax.tree.structure(sess.opt)
    if n != treedef.num_leaves:
        raise ValueError(
            f"checkpoint has {n} optimizer leaves, session expects "
            f"{treedef.num_leaves} — sync/arch mismatch?")
    leaves = [np.asarray(opt_arrs[f"leaf_{i}"]) for i in range(n)]
    state = jax.tree.unflatten(treedef, leaves)
    if sess.mode == "zero1":
        saved_world = int(man.get("world", sess.world))
        if saved_world != sess.world:
            state = resize_zero1_state(state, sess.params, sess.world,
                                       sess.sync)
    sess.opt = jax.device_put(jax.tree.map(jnp.asarray, state),
                              sess.built.opt_spec(sess.params))
    return s, man
