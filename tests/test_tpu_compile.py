"""The Pallas round kernels compile for a TPU v5e, without the chip.

Each kernel is lowered with ``interpret=False`` at a real gradient-sync
width (one 25 MB f32 bucket split over p=4 ranks: 1,562,500 columns;
the qwen3-1.7b embedding leaf over p=4: 77,791,232) for a described
``v5e:2x2`` topology and compiled by the TPU compiler; the compiled HLO
must hold the kernel as a ``tpu_custom_call`` instruction named by its
``pallas_call(name=...)``, the name a profiler trace shows it under.
Numbers are checked on the chip by ``chip_smoke.py``; here only the
compiler's verdict counts (block shapes it refuses, scoped-memory
limits, unsupported layouts).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import compat
from repro.kernels import (DEFAULT_GROUP, fused_round, fused_round_dq,
                           permute_rows, quantize_rows, wire_ngroups)
from repro.kernels.block_reduce import (DEFAULT_COL_TILE as BLOCK_COL_TILE,
                                        block_reduce)
from repro.kernels.quantize import dequant_add, quantize

BUCKET_COLS = 1_562_500
EMBED_COLS = 77_791_232


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = compat.tpu_topology("v5e:2x2")
    except Exception as e:  # no TPU compiler / library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off.
    with compat.persistent_cache_off():
        yield topo


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _cp(cols: int) -> int:
    return cols + (-cols) % DEFAULT_GROUP


def _tiled(cols: int) -> int:
    return cols + (-cols) % BLOCK_COL_TILE


F32, BF16, I8 = jnp.float32, jnp.bfloat16, jnp.int8

#: case -> (function, argument shapes, the kernel's ``name=``)
CASES = {
    "quantize[f32,bucket,8 rows]": (
        functools.partial(quantize, interpret=False),
        [((8, BUCKET_COLS), F32)], "quantize"),
    "quantize_rows[bf16,embedding]": (
        functools.partial(quantize_rows, interpret=False),
        [((2, EMBED_COLS), BF16)], "quantize"),
    "dequant_add[f32,bucket]": (
        functools.partial(dequant_add, interpret=False),
        [((2, BUCKET_COLS), F32), ((2, BUCKET_COLS), I8),
         ((2, wire_ngroups(BUCKET_COLS)), F32)], "dequant_add"),
    # (lo, nb, next_lo) = (2, 2, 1): the send row is a slice of a folded
    # two-row buffer; (3, 1, 2): the send row is copied through.
    "fused_round_dq[2,2,1]": (
        functools.partial(fused_round_dq, nb=2, next_lo=1, interpret=False),
        [((2, _cp(BUCKET_COLS)), F32), ((2, _cp(BUCKET_COLS)), I8),
         ((2, _cp(BUCKET_COLS) // DEFAULT_GROUP), F32)], "fused_round_dq"),
    "fused_round_dq[3,1,2]": (
        functools.partial(fused_round_dq, nb=1, next_lo=2, interpret=False),
        [((3, _cp(BUCKET_COLS)), F32), ((1, _cp(BUCKET_COLS)), I8),
         ((1, _cp(BUCKET_COLS) // DEFAULT_GROUP), F32)], "fused_round_dq"),
    "fused_round[bf16,2,2,1]": (
        functools.partial(fused_round, nb=2, next_lo=1, interpret=False),
        [((2, BUCKET_COLS), BF16), ((2, BUCKET_COLS), BF16)],
        "fused_round"),
    "permute_rows[f32,embedding]": (
        functools.partial(permute_rows, perm=(3, 2, 1, 0), interpret=False),
        [((4, EMBED_COLS), F32)], "permute_rows"),
    # ops.fused_block_reduce pads the columns to the column tile
    "block_reduce[f32,bucket]": (
        functools.partial(block_reduce, op="add", interpret=False),
        [((8, _tiled(BUCKET_COLS)), F32)] * 2, "block_reduce"),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes, kernel = CASES[name]
    text = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text
    assert re.search(rf"%{kernel}(\.\d+)? = .*custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"', text), kernel


def _step_text(**kw) -> str:
    """The compiled HLO text of a ZeRO-1 step built by ``build_session``
    over the described chips, from shapes alone."""
    from jax.sharding import NamedSharding

    from repro.launch import bootstrap

    sess = bootstrap.build_session(**kw)
    built = sess.built
    shapes = jax.eval_shape(sess.model.init, jax.random.PRNGKey(0))

    def abstract(tree, shardings):
        return jax.tree.map(lambda a, sh: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sh), tree, shardings)

    params = abstract(shapes, built.param_sharding(shapes))
    opt = abstract(jax.eval_shape(built.init_opt, shapes),
                   built.opt_spec(shapes))
    batch_sh = NamedSharding(sess.mesh, built.batch_spec)
    batch = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=batch_sh),
        sess.pipe.batch_at(0))
    with compat.use_mesh(sess.mesh):
        return built.step_fn.lower(params, opt, batch).compile().as_text()


def test_zero1_step_with_auto_model_axis_runs_jnp_rounds(v5e_2x2):
    """On a dp=2 x mp=2 mesh the ZeRO-1 step keeps its model axis Auto,
    where the TPU compiler cannot partition a Pallas kernel: an explicit
    ``use_fused_kernel=True`` is refused when the step is built, and the
    default (auto) step compiles with jnp rounds."""
    from repro.launch import bootstrap

    kw = dict(arch="qwen3-1.7b", scale_down=True, seq_len=32,
              global_batch=2, dp=2, mp=2, devices=v5e_2x2.devices,
              init_state=False)
    with pytest.raises(ValueError, match="Mosaic"):
        bootstrap.build_session(use_fused_kernel=True, **kw)
    text = _step_text(**kw)
    assert "collective-permute" in text
    assert "tpu_custom_call" not in text


def test_zero1_step_names_its_phases(v5e_2x2, monkeypatch):
    """The dp=4 step (fully manual, fused round kernels) carries the
    ``zero1.*`` named scopes in its HLO metadata, where a trace reader
    finds them: every collective-permute belongs to the reduce-scatter
    or the allgather, and every round kernel, named ``fused_round``, to
    the reduce-scatter."""
    import importlib
    fr = importlib.import_module("repro.kernels.fused_round")
    # The process's backend is the CPU; compile the kernels as the chip
    # runs them.
    monkeypatch.setattr(fr, "_interpret_default", lambda: False)
    text = _step_text(arch="qwen3-1.7b", scale_down=True, seq_len=32,
                      global_batch=4, dp=4, mp=1, devices=v5e_2x2.devices,
                      init_state=False, grad_sync="circulant",
                      use_fused_kernel=True)
    scope = re.compile(r'op_name="[^"]*(zero1\.[a-z_]+)/')

    def scopes(pattern):
        lines = [ln for ln in text.splitlines() if re.search(pattern, ln)]
        assert lines, pattern
        return {m.group(1) if m else None
                for m in map(scope.search, lines)}

    assert scopes(r" collective-permute(-start)?\(") == {
        "zero1.reduce_scatter", "zero1.allgather"}
    assert scopes(r"%fused_round(\.\d+)? = .*tpu_custom_call") == {
        "zero1.reduce_scatter"}
    for name in ("zero1.fwd_bwd", "zero1.adamw"):
        assert f"{name}/" in text, name
