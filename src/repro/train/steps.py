"""Train-step builders — three execution modes over one model API.

  zero1      partial-manual shard_map: data axes MANUAL (the paper's
             circulant collectives drive grad reduce-scatter + param
             allgather; optimizer state sharded 1/P), model axis AUTO
             (GSPMD tensor-parallel).  Default for archs whose TP-sharded
             params fit per chip.
  fsdp_auto  pure GSPMD: params/m/v sharded over (data+model) via
             NamedSharding; XLA inserts its own collectives.  For the
             >=90B archs.
  single     plain jit, no mesh — CPU smoke tests and the quickstart.

Every mode returns (step_fn, init_opt_fn, shardings) with the same
signature:  step_fn(params, opt, batch) -> (params, opt, metrics).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import compat
from repro.models import ModelApi, ShardingRecipe, make_param_specs
from repro.optim import adamw as adamw_mod
from repro.optim.adamw import (AdamWConfig, AdamState, TreeAdamState,
                               init_state, init_tree_state, update_tree)
from repro.optim.zero1 import (GradSyncConfig, Zero1State, init_zero1_state,
                               zero1_state_specs, zero1_step)


@dataclass
class BuiltStep:
    """``param_sharding`` (params -> sharding tree) is where the step
    returns the params; placing them there before step 0 (as
    ``launch.bootstrap.build_session`` does) keeps step 1 on step 0's
    compiled program.  ``opt_spec`` is the same for the optimizer state."""

    step_fn: Callable          # (params, opt, batch) -> (params, opt, metrics)
    init_opt: Callable         # (params) -> opt state (matching sharding)
    batch_spec: Any = None
    opt_spec: Any = None
    param_sharding: Any = None


def flat_param_len(params, world: int) -> int:
    """Padded fused-gradient length (static, from leaf shapes)."""
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    return n + ((-n) % world)


def collective_specs(sync: GradSyncConfig, model_cfg=None,
                     ep_world: int | None = None
                     ) -> tuple[tuple[str, Any], ...]:
    """Every :class:`CollectiveSpec` a zero1 step executes, as
    ``(role, spec)`` pairs.

    Role ``"data"``: the grad-sync reduce-scatter/allgather pair — one
    plan per data axis.  Role ``"ep"``: the MoE expert-dispatch
    alltoall(v) pair, present only when ``model_cfg`` uses
    ``moe_dispatch='ep'`` (``ep_world`` is that axis's size).  This is
    the ONE enumeration both the ``build_zero1`` pre-flight and the
    elastic controller's re-plan (``ft.elastic.active_specs``) consume,
    so a spec added to the step cannot silently skip either verifier.
    """
    out: list[tuple[str, Any]] = [("data", sync.rs_spec()),
                                  ("data", sync.ag_spec())]
    if model_cfg is not None and getattr(model_cfg, "is_moe", False) \
            and getattr(model_cfg, "moe_dispatch", "global") == "ep":
        if ep_world is None:
            raise ValueError(
                "moe_dispatch='ep' config needs ep_world to enumerate its "
                "dispatch specs")
        from repro.models.dispatch import ep_collective_specs
        out += [("ep", sp) for sp in ep_collective_specs(model_cfg, ep_world)]
    return tuple(out)


# ---------------------------------------------------------------------------
# single (no mesh)
# ---------------------------------------------------------------------------

def build_single(model: ModelApi, opt_cfg: AdamWConfig) -> BuiltStep:
    @jax.jit
    def step_fn(params, opt, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        new_params, new_opt, gnorm = update_tree(opt_cfg, opt, grads, params)
        return new_params, new_opt, {"loss": loss, "grad_norm": gnorm,
                                     "lr": adamw_mod.lr_at(opt_cfg,
                                                           new_opt.step)}

    device = SingleDeviceSharding(jax.devices()[0])
    return BuiltStep(step_fn=step_fn, init_opt=init_tree_state,
                     opt_spec=lambda params: device,
                     param_sharding=lambda params: device)


# ---------------------------------------------------------------------------
# zero1 (manual data axes via the paper's collectives)
# ---------------------------------------------------------------------------

def resolve_zero1_sync(sync: GradSyncConfig, *, auto_axes: bool,
                       platform: str) -> GradSyncConfig:
    """The grad-sync config a zero1 step runs on ``platform``.

    The TPU compiler cannot partition a Pallas (Mosaic) kernel over an
    Auto mesh axis, so when the step keeps its model axis Auto the
    circulant rounds run in jnp on a TPU: auto selection
    (``use_fused_kernel=None``) picks jnp there, and an explicit
    ``use_fused_kernel=True`` is refused here rather than in the TPU
    compiler.  Elsewhere the kernels run in interpret mode, which GSPMD
    partitions like any other op."""
    if not auto_axes or platform != "tpu":
        return sync
    if sync.use_fused_kernel:
        raise ValueError(
            "use_fused_kernel=True cannot run in a zero1 step whose model "
            "axis stays Auto on a TPU: Mosaic kernels cannot be "
            "automatically partitioned.  Use a model axis of 1, or "
            "use_fused_kernel=None/False (jnp rounds).")
    from dataclasses import replace
    return replace(sync, use_fused_kernel=False)


def build_zero1(model: ModelApi, mesh: Mesh, recipe: ShardingRecipe,
                opt_cfg: AdamWConfig, sync: GradSyncConfig,
                remat: bool = True) -> BuiltStep:
    # Expert-parallel MoE dispatch exchanges over cfg.ep_axis INSIDE the
    # step, so that axis must be manual too; ep and a model axis of size
    # 1 run the region fully manual (see the inner model below).
    ep = (model.cfg.is_moe
          and getattr(model.cfg, "moe_dispatch", "global") == "ep")
    fully_manual = ep or mesh.shape[recipe.model_axis] == 1
    sync = resolve_zero1_sync(sync, auto_axes=not fully_manual,
                              platform=mesh.devices.flat[0].platform)

    # Collective order: fastest axis first (intra-pod before cross-pod) so
    # the full-volume first RS phase stays on fast links (DESIGN §2).
    collective_axes = tuple(reversed(recipe.data_axes))
    world = int(np.prod([mesh.shape[a] for a in recipe.data_axes]))

    # Compile the grad-sync CollectivePlans up front: a bad sync config
    # (unknown schedule, wire×op conflict, ...) fails HERE with a config
    # error instead of mid-trace, and the per-axis plans are warm in the
    # cache before the first step traces.  Each plan then goes through
    # the static verifier (Theorem 1 partition, deadlock-freedom, row
    # tables) — the same pre-flight an elastic re-plan at a new world
    # size would run before trusting the fresh geometry.
    from repro.analysis.verify import assert_verified
    from repro.core.plan import plan as _plan
    for ax in collective_axes:
        for role, sp in collective_specs(sync):
            assert_verified(_plan(sp, p=mesh.shape[ax], axis_name=ax))

    # Bucketed sync: compute the static bucket partition from the model's
    # abstract param shapes NOW (jax.eval_shape — no allocation) so a bad
    # bucket_bytes / partition fails at build time, not mid-trace, and
    # assert every bucket's segments are well-formed.  The RS/AG plans
    # verified above are the ones each bucket executes (plan geometry is
    # shape-independent, so one cached plan serves every bucket).
    abs_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if sync.bucket_bytes is not None:
        from repro.optim.zero1 import is_zero_leaf, plan_grad_buckets
        zshapes = [l.shape for l in jax.tree.leaves(abs_params)
                   if is_zero_leaf(l.shape, world, sync.min_shard_numel)]
        buckets = plan_grad_buckets(zshapes, world, sync.bucket_bytes,
                                    jnp.dtype(sync.rs_dtype).itemsize)
        covered = {}
        for b in buckets:
            if not b:
                raise ValueError("bucket partitioner produced empty bucket")
            for (li, lo, hi) in b:
                if not 0 <= lo < hi:
                    raise ValueError(f"bad segment ({li}, {lo}, {hi})")
                covered[li] = covered.get(li, 0) + (hi - lo)
        for li, shape in enumerate(zshapes):
            rows = (shape[0] + (-shape[0]) % world) // world
            if covered.get(li, 0) != rows:
                raise ValueError(
                    f"bucket partition covers {covered.get(li, 0)}/{rows} "
                    f"shard rows of leaf {li} {shape}")

    # The ep axis's alltoall(v) plans fail fast / pre-warm here, like
    # the grad-sync plans above.
    if ep:
        ep_axis = model.cfg.ep_axis
        if ep_axis not in mesh.shape:
            raise ValueError(
                f"moe_dispatch='ep' exchanges over mesh axis {ep_axis!r}, "
                f"which is not in mesh {dict(mesh.shape)}")
        for role, sp in collective_specs(sync, model.cfg,
                                         mesh.shape[ep_axis]):
            if role == "ep":
                assert_verified(_plan(sp, p=mesh.shape[ep_axis],
                                      axis_name=ep_axis))

    # Inside the manual region the data axes are already per-shard: the
    # inner model must only constrain over the AUTO (model) axis.  ep
    # dispatch needs its exchange axis manual, so it takes the
    # fully-manual route (expert weights replicated per rank; each rank
    # slices its own experts inside the region).  So does a model axis of
    # size 1, where both routes compute the same thing: the TPU compiler
    # cannot partition a Pallas kernel over an AUTO axis, so the fused
    # round kernels compile only in a fully-manual region.
    from dataclasses import replace as _dc_replace
    from repro.models import build as _build_model
    if fully_manual:
        inner_recipe = None
        manual_axes = None  # full manual
    else:
        inner_recipe = _dc_replace(recipe, data_axes=())
        manual_axes = set(recipe.data_axes)
    inner_model = _build_model(model.cfg, recipe=inner_recipe, remat=remat)

    def constrain(tree):
        """Reduced grad shards -> the params' model-axis sharding."""
        if inner_recipe is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            inner_model.param_specs(tree))

    def inner(params, opt, batch):
        return zero1_step(
            jax.value_and_grad(inner_model.loss), params, opt, batch,
            axis_names=collective_axes, opt_cfg=opt_cfg, sync=sync,
            constrain=constrain)

    # Manual-axis specs: params replicated over data axes (model axis is
    # auto — rides on the arrays' NamedShardings); batch sharded over data;
    # opt m/v PER-LEAF sharded over dim 0 (zero leaves) or replicated
    # (tiny leaves / the no-ZeRO allreduce baseline).  With compressed
    # gradient sync (sync.wire == 'int8') + error feedback, the opt state
    # additionally carries per-rank EF residuals (Zero1State.ef) whose
    # leading axis is sharded one-row-per-rank over the data axes —
    # zero1_state_specs emits the matching specs, so the shard_map
    # in/out_specs below pick them up with no special-casing here.
    pspec = P()
    batch_spec = P(recipe.data_axes)

    def batch_specs_for(batch):
        return jax.tree.map(lambda _: batch_spec, batch)

    def opt_specs_for(params):
        return zero1_state_specs(params, world, sync, collective_axes)

    def opt_sharding(params):
        ospecs = opt_specs_for(params)
        return jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs,
                            is_leaf=lambda x: isinstance(x, P))

    def param_sharding(params):
        # ep regions are fully manual over replicated params; otherwise
        # the recipe's tensor-parallel specs over the model axis.
        if ep:
            return jax.tree.map(lambda _: NamedSharding(mesh, P()), params)
        return _recipe_sharding(model, mesh, recipe, params)

    # The step returns params and optimizer state at the shardings it
    # declares (``param_sharding``/``opt_sharding``), so inputs placed
    # there before step 0 make every later step hit step 0's program.
    out_shardings = (param_sharding(abs_params), opt_sharding(abs_params),
                     NamedSharding(mesh, P()))

    # NB: must run under jit — EAGER shard_map dispatch with
    # check_vma=False + partial-auto axes trips an internal _unmatch spec
    # check (it builds P(all mesh axes) but validates against manual-only).
    @functools.partial(jax.jit, out_shardings=out_shardings)
    def step_fn(params, opt, batch):
        ospecs = opt_specs_for(params)
        f = compat.shard_map(
            inner, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: pspec, params), ospecs,
                      batch_specs_for(batch)),
            out_specs=(jax.tree.map(lambda _: pspec, params), ospecs,
                       {"loss": P(), "grad_norm": P(), "lr": P()}),
            axis_names=manual_axes,
            check_vma=False)
        return f(params, opt, batch)

    def init_opt(params):
        return init_zero1_state(params, world, sync)

    return BuiltStep(
        step_fn=step_fn, init_opt=init_opt,
        batch_spec=batch_spec,
        opt_spec=opt_sharding,
        param_sharding=param_sharding,
    )


# ---------------------------------------------------------------------------
# fsdp_auto (pure GSPMD)
# ---------------------------------------------------------------------------

def build_fsdp_auto(model: ModelApi, mesh: Mesh, recipe: ShardingRecipe,
                    opt_cfg: AdamWConfig) -> BuiltStep:
    batch_spec = P(recipe.data_axes)

    def param_sharding(params):
        return _recipe_sharding(model, mesh, recipe, params)

    def opt_sharding(params):
        ps = param_sharding(params)  # m/v shard like params
        return TreeAdamState(m=ps, v=ps, step=NamedSharding(mesh, P()))

    abs_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    @functools.partial(jax.jit, out_shardings=(
        param_sharding(abs_params), opt_sharding(abs_params),
        NamedSharding(mesh, P())))
    def step_fn(params, opt, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        new_params, new_opt, gnorm = update_tree(opt_cfg, opt, grads, params)
        return new_params, new_opt, {"loss": loss, "grad_norm": gnorm,
                                     "lr": adamw_mod.lr_at(opt_cfg,
                                                           new_opt.step)}

    return BuiltStep(step_fn=step_fn, init_opt=init_tree_state,
                     batch_spec=batch_spec, opt_spec=opt_sharding,
                     param_sharding=param_sharding)


def _recipe_sharding(model: ModelApi, mesh: Mesh, recipe: ShardingRecipe,
                     params):
    """NamedShardings of the recipe's param specs, with every axis that
    does not divide its dimension dropped (``launch.mesh.sanitize_specs``)."""
    from repro.launch.mesh import named, sanitize_specs
    return named(mesh, sanitize_specs(mesh, model.param_specs(params), params,
                                      model_axis=recipe.model_axis))


def build(mode: str, model: ModelApi, opt_cfg: AdamWConfig,
          mesh: Mesh | None = None, recipe: ShardingRecipe | None = None,
          sync: GradSyncConfig | None = None, remat: bool = True) -> BuiltStep:
    if mode == "single":
        return build_single(model, opt_cfg)
    if mode == "zero1":
        return build_zero1(model, mesh, recipe, opt_cfg,
                           sync or GradSyncConfig(), remat=remat)
    if mode == "fsdp_auto":
        return build_fsdp_auto(model, mesh, recipe, opt_cfg)
    raise ValueError(f"unknown mode {mode}")
