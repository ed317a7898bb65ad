"""Cross-implementation conformance harness for the paper's collectives.

Sweeps every (collective × impl × schedule × op × dtype ×
use_fused_kernel × wire_dtype) combination that is meaningful for a given
axis size ``p`` — int8-wire mirrors use tolerance-based assertions
(compressed rounds are lossy by design) while everything else keeps its
exact checks — plus the alltoall(v) sweep (``run_alltoall``: uniform
blocks and ragged per-pair counts matrices vs the simulator, the host
transpose reference and XLA's native all-to-all, all bitwise) and, for
composite p, a hierarchical two-axis sweep (``run_hierarchical``).  Per
case it asserts:

  (a) agreement with a host-side numpy reference — bitwise for integer and
      order-independent (max/min) reductions, tolerance-based for float
      summation — and, where XLA provides a native baseline (psum_scatter /
      psum / pmax / pmin), agreement with that baseline too;
  (b) for the circulant implementations, that the lowered HLO contains
      exactly ``rounds(schedule)`` collective-permute ops for
      reduce-scatter and ``2 * rounds(schedule)`` for allreduce, where for
      the ceil(log2 p)-round schedules (halving / power2) ``rounds ==
      ceil_log2(p)`` — Theorems 1 and 2 machine-checked at every tested p,
      non-powers-of-two included (they are the paper's whole point).

The numeric checks need ``p`` fake XLA devices, which must be configured
before the first jax import; run this module as its own process:

    python src/repro/core/conformance.py <p>

``tests/test_conformance.py`` drives one subprocess per p in
``DEFAULT_PS``.
"""
import os
import sys

if __name__ == "__main__":  # set device count BEFORE the jax import below
    import re as _re
    _CLI_P = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    # Strip any inherited device-count flag: XLA keeps the LAST occurrence,
    # so a caller's exported =8 would silently override the requested p.
    _inherited = _re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                         os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_CLI_P} " + _inherited)
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import math  # noqa: E402
from dataclasses import dataclass  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import compat  # noqa: E402
from repro.core import collectives as C  # noqa: E402
from repro.core import simulator as sim  # noqa: E402
from repro.core.schedule import ceil_log2, get_skips  # noqa: E402
from repro.core.spec import CollectiveSpec  # noqa: E402

# Non-powers-of-two dominate by design — power-of-two p is the case the
# classic algorithms already handle; the paper's claim is the general one.
DEFAULT_PS = (2, 3, 4, 5, 6, 7, 8, 12, 16)
SCHEDULES = ("halving", "power2", "fully_connected", "sqrt", "two_level")
OPTIMAL_SCHEDULES = ("halving", "power2")   # exactly ceil(log2 p) rounds
OPS = ("add", "max", "min")
DTYPES = ("float32", "bfloat16", "int32")

AXIS = "x"
BLK = 4  # elements per block — tiny on purpose; compile time dominates

_NP_OPS = {"add": np.add, "max": np.maximum, "min": np.minimum}


def two_level_group(p: int) -> int:
    """Intra-group size for the two_level schedule: the divisor of p
    nearest sqrt(p).  1 for primes (two_level degenerates to halving)."""
    divisors = [d for d in range(2, p) if p % d == 0]
    if not divisors:
        return 1
    return min(divisors, key=lambda d: (abs(d - math.sqrt(p)), d))


def schedule_rounds(p: int, schedule: str) -> int:
    """Round count of ``schedule`` at ``p`` ranks (two_level resolves
    its group size first)."""
    group = two_level_group(p) if schedule == "two_level" else None
    return len(get_skips(p, schedule, group=group))


@dataclass(frozen=True)
class Case:
    """One conformance-matrix cell: a (collective, impl, schedule, op,
    dtype, fused, wire) combination to execute and check."""
    collective: str            # reduce_scatter | allreduce
    impl: str                  # circulant | ring | recursive_halving | xla
    schedule: str = "halving"
    op: str = "add"
    dtype: str = "float32"
    fused: bool = False        # use_fused_kernel (circulant only)
    wire: str | None = None    # wire_dtype (circulant only; float dtypes)

    @property
    def label(self) -> str:
        tag = (":fused" if self.fused else "") + \
            (f":wire={self.wire}" if self.wire else "")
        return (f"{self.collective}[{self.impl}:{self.schedule}"
                f":{self.op}:{self.dtype}{tag}]")


def sweep_cases(p: int) -> list[Case]:
    """Every meaningful combination for axis size p, deduplicated: impls ×
    both collectives at the defaults, then schedule / op / dtype sweeps on
    the circulant implementation (the component under test).  Every
    circulant case is mirrored with ``use_fused_kernel=True`` so the fused
    Pallas round kernel is held to the exact same reference checks, and
    every float circulant case (fused and not) is additionally mirrored
    with ``wire_dtype="int8"`` — the compressed rounds are asserted
    against the same references with quantization-aware tolerances."""
    pow2 = p & (p - 1) == 0
    cases: list[Case] = []
    for coll in ("reduce_scatter", "allreduce"):
        impls = ["circulant", "ring", "xla"]
        if coll == "reduce_scatter" and pow2 and p > 1:
            impls.append("recursive_halving")
        base = [Case(coll, impl) for impl in impls]
        base.extend(Case(coll, "circulant", schedule=s)
                    for s in SCHEDULES if s != "halving")
        base.extend(Case(coll, "circulant", op=op)
                    for op in OPS if op != "add")
        base.extend(Case(coll, "circulant", dtype=dt)
                    for dt in DTYPES if dt != "float32")
        base.extend(
            Case(c.collective, c.impl, c.schedule, c.op, c.dtype, fused=True)
            for c in list(base) if c.impl == "circulant")
        base.extend(
            Case(c.collective, c.impl, c.schedule, c.op, c.dtype,
                 fused=c.fused, wire="int8")
            for c in list(base)
            if c.impl == "circulant" and c.dtype != "int32")
        cases.extend(base)
    return cases


# ---------------------------------------------------------------------------
# Execution helpers
# ---------------------------------------------------------------------------

def _shmap1(mesh, fn, check_vma: bool | None = None):
    """Per-rank fn over a (p, ...) global sharded on axis 0 (the repo's
    standard v[0]-unwrap convention).  ``check_vma=False`` is passed only
    for the fused (pallas_call) cases, so the jnp/baseline cases keep
    exercising the replication checker."""
    return jax.jit(compat.shard_map(
        lambda v: fn(v[0])[None], mesh=mesh,
        in_specs=(P(AXIS),), out_specs=P(AXIS), check_vma=check_vma))


def case_spec(case: Case, p: int) -> CollectiveSpec:
    """The CollectiveSpec a sweep case means — every case executes
    through the plan/execute API (the component under test)."""
    if case.impl != "circulant":
        return CollectiveSpec(kind=case.impl, op=case.op)
    return CollectiveSpec(
        kind="circulant", schedule=case.schedule, op=case.op,
        use_fused_kernel=case.fused, wire_dtype=case.wire,
        group=two_level_group(p) if case.schedule == "two_level" else None)


def _impl_fn(case: Case, p: int):
    spec = case_spec(case, p)
    if case.collective == "reduce_scatter":
        return lambda v: C.reduce_scatter(v, AXIS, spec=spec)
    return lambda v: C.allreduce(v, AXIS, spec=spec)


def _xla_baseline_fn(case: Case):
    """Native-XLA reference for the same collective, when one exists."""
    if case.collective == "reduce_scatter":
        if case.op == "add":
            return lambda v: C.xla_reduce_scatter(v, AXIS)
        return None  # psum_scatter is add-only
    if case.op == "add":
        return lambda v: C.xla_allreduce(v, AXIS)
    red = lax.pmax if case.op == "max" else lax.pmin
    return lambda v: red(v, AXIS)


def _make_input(case: Case, p: int, rng: np.random.Generator) -> np.ndarray:
    n = p * BLK
    if case.dtype == "int32":
        return rng.integers(-50, 50, size=(p, n), dtype=np.int64).astype(
            np.int32)
    x = rng.standard_normal((p, n)).astype(np.float32)
    if case.dtype == "bfloat16":
        x = np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))
    return x


def _reference(case: Case, xg: np.ndarray) -> np.ndarray:
    """Host ground truth: op-fold over ranks (float64 accumulation for
    float inputs; exact dtype for integers)."""
    npop = _NP_OPS[case.op]
    work = xg.astype(np.float64) if case.dtype != "int32" else xg
    red = work[0]
    for r in range(1, xg.shape[0]):
        red = npop(red, work[r])
    return red


def _tolerances(case: Case, p: int) -> dict:
    if case.wire == "int8":
        # Quantization-bounded, NOT bitwise (even for max/min): every
        # round requantizes partial sums, so the error budget scales with
        # the round count and the partial-sum magnitude (~sqrt(p) for the
        # N(0,1) inputs).  The bound below holds with ~5x margin at every
        # tested (p, schedule); bf16 inputs are strictly coarser than the
        # int8 grid error so they need no extra term.
        return {"rtol": 0.1, "atol": 0.05 * p + 0.1}
    if case.dtype == "int32" or case.op in ("max", "min"):
        return {"rtol": 0, "atol": 0}
    if case.dtype == "bfloat16":
        return {"rtol": 0.05, "atol": 0.05 * p}
    return {"rtol": 2e-5, "atol": 2e-5}


def run_case(mesh, p: int, case: Case, rng: np.random.Generator) -> None:
    """Execute one case and assert agreement; raises AssertionError with
    the case label on any mismatch."""
    xg = _make_input(case, p, rng)
    dt = jnp.dtype(case.dtype)
    out = np.asarray(_shmap1(mesh, _impl_fn(case, p),
                             check_vma=False if case.fused else None)(
        jnp.asarray(xg, dtype=dt)))
    ref = _reference(case, xg)
    tol = _tolerances(case, p)
    try:
        if case.collective == "reduce_scatter":
            ref_blocks = ref.reshape(p, BLK)
            for r in range(p):
                np.testing.assert_allclose(
                    out[r].astype(np.float64), ref_blocks[r], **tol)
        else:
            for r in range(p):
                np.testing.assert_allclose(
                    out[r].astype(np.float64), ref, **tol)
                # Theorem 2's output is REPLICATED — bitwise, not just close.
                np.testing.assert_array_equal(out[r], out[0])
    except AssertionError as e:
        raise AssertionError(f"{case.label} vs host reference (p={p}): {e}") \
            from None

    base_fn = _xla_baseline_fn(case)
    if base_fn is None:
        return
    base = np.asarray(_shmap1(mesh, base_fn)(jnp.asarray(xg, dtype=dt)))
    try:
        if case.wire is None and (case.dtype == "int32"
                                  or case.op in ("max", "min")):
            np.testing.assert_array_equal(out, base)  # bitwise
        else:
            np.testing.assert_allclose(out.astype(np.float64),
                                       base.astype(np.float64), **tol)
    except AssertionError as e:
        raise AssertionError(f"{case.label} vs XLA baseline (p={p}): {e}") \
            from None


# ---------------------------------------------------------------------------
# HLO structure: Theorem 1/2 round counts
# ---------------------------------------------------------------------------

def _n_collective_permutes(jitted, shape: tuple[int, ...]) -> int:
    """Lowered-HLO collective-permute count of a jitted per-rank wrapper
    on an f32 input of ``shape`` (the repo-wide counter lives in
    ``repro.analysis.hlo_budget``; this shim fixes the f32 dtype)."""
    from repro.analysis.hlo_budget import count_collective_permutes_lowered
    return count_collective_permutes_lowered(jitted, shape)


def count_collective_permutes(mesh, p: int, fn,
                              check_vma: bool | None = None) -> int:
    """Collective-permute count of ``fn`` lowered under shard_map on
    ``mesh`` with the standard (p, p*BLK) conformance payload."""
    return _n_collective_permutes(_shmap1(mesh, fn, check_vma=check_vma),
                                  (p, p * BLK))


def check_round_counts(mesh, p: int) -> dict[str, tuple[int, int]]:
    """Assert RS/AR collective-permute counts for every schedule, on the
    jnp and fused-Pallas round paths AND the int8 wire format (neither
    fusion nor compression may change the communication structure — the
    packed [codes | scale bytes] wire buffer keeps one collective-permute
    per round); returns {schedule[:fused][:w8]: (n_rs, n_ar)}."""
    results = {}
    for sched in SCHEDULES:
        kw = {"schedule": sched}
        if sched == "two_level":
            kw["group"] = two_level_group(p)
        rounds = schedule_rounds(p, sched)
        if sched in OPTIMAL_SCHEDULES:
            assert rounds == ceil_log2(p), \
                f"{sched} must be a ceil(log2 p)-round schedule (p={p})"
        for fused in (False, True):
            for wire in (None, "int8"):
                kwf = dict(kw, use_fused_kernel=fused, wire_dtype=wire)
                cv = False if fused else None
                tag = sched + (":fused" if fused else "") + \
                    (":w8" if wire else "")
                n_rs = count_collective_permutes(
                    mesh, p,
                    lambda v, kwf=kwf: C.circulant_reduce_scatter(
                        v, AXIS, **kwf),
                    check_vma=cv)
                n_ar = count_collective_permutes(
                    mesh, p,
                    lambda v, kwf=kwf: C.circulant_allreduce(v, AXIS, **kwf),
                    check_vma=cv)
                assert n_rs == rounds, \
                    (f"RS[{tag}] p={p}: {n_rs} collective-permutes, "
                     f"want {rounds} (Theorem 1)")
                assert n_ar == 2 * rounds, \
                    (f"AR[{tag}] p={p}: {n_ar} collective-permutes, "
                     f"want {2 * rounds} (Theorem 2)")
                results[tag] = (n_rs, n_ar)
    return results


# ---------------------------------------------------------------------------
# Non-uniform counts (paper Corollary 3) — spec(counts=...) vs simulator
# ---------------------------------------------------------------------------

NONUNIFORM_SCHEDULES = ("halving", "power2", "fully_connected")


def nonuniform_counts_cases(p: int) -> dict[str, tuple[int, ...]]:
    """Per-rank block-size patterns for the Corollary 3 sweep.

    ``one_column`` is the paper's worst case (every element concentrated
    in a single column — each round one rank ships the whole vector);
    ``zero_ranks`` exercises empty blocks; ``ragged`` is a deterministic
    mixed pattern; ``uniform`` must agree with the uniform path.
    """
    ragged = tuple((i * 5 + 3) % 7 for i in range(p))
    if sum(ragged) == 0:
        ragged = (1,) * p
    one_col = [0] * p
    one_col[p // 2] = 4 * p + 3
    zero_ranks = tuple(0 if i % 2 else i + 2 for i in range(p))
    if sum(zero_ranks) == 0:
        zero_ranks = (2,) + (0,) * (p - 1)
    return {
        "ragged": ragged,
        "one_column": tuple(one_col),
        "zero_ranks": zero_ranks,
        "uniform": (BLK,) * p,
    }


def run_nonuniform(p: int, mesh, verbose: bool = False) -> dict:
    """Corollary 3 conformance: ``CollectiveSpec(counts=...)`` reduce-
    scatter (and allreduce) under shard_map vs the numpy simulator (which
    asserts the Theorem 1 counters) AND the host reference, across
    schedules × ops × counts patterns, plus lowered-HLO collective-
    permute counts — still exactly ``rounds(schedule)`` (= ceil(log2 p)
    for halving/power2): ragged counts must not change the communication
    structure."""
    rng = np.random.default_rng(4242 + p)
    n_cases = 0
    rounds: dict[str, tuple[int, int]] = {}
    for name, counts in nonuniform_counts_cases(p).items():
        N, bmax = sum(counts), max(counts)
        offs = np.concatenate([[0], np.cumsum(counts)])
        xg = rng.standard_normal((p, N)).astype(np.float32)
        inputs = [[xg[r, offs[i]:offs[i + 1]] for i in range(p)]
                  for r in range(p)]
        for sched in NONUNIFORM_SCHEDULES:
            for op in ("add", "max"):
                spec = CollectiveSpec(schedule=sched, op=op, counts=counts)
                tag = f"counts[{name}:{sched}:{op}]"
                W, stats = sim.simulate_reduce_scatter(
                    inputs, op=_NP_OPS[op], schedule=sched)
                if sched in OPTIMAL_SCHEDULES:
                    stats.assert_theorem1(p)
                else:
                    assert stats.rounds == schedule_rounds(p, sched)
                    assert all(b == p - 1 for b in stats.blocks_sent)
                out = np.asarray(_shmap1(
                    mesh, lambda v, s=spec: C.reduce_scatter(
                        v, AXIS, spec=s))(jnp.asarray(xg)))
                ref = _ref_nonuniform(xg, op)
                tol = ({"rtol": 0, "atol": 0} if op != "add"
                       else {"rtol": 2e-5, "atol": 2e-5})
                for r in range(p):
                    c = counts[r]
                    np.testing.assert_allclose(
                        out[r, :c].astype(np.float64), W[r], **tol,
                        err_msg=f"{tag} vs simulator (p={p}, rank {r})")
                    np.testing.assert_allclose(
                        out[r, :c].astype(np.float64),
                        ref[offs[r]:offs[r] + c], **tol,
                        err_msg=f"{tag} vs host reference (p={p}, rank {r})")
                    assert (out[r, c:] == 0).all(), \
                        f"{tag}: rows past counts[{r}] must be zero"
                n_cases += 1
        # Allreduce (RS + non-uniform allgather) on the default schedule:
        # replicated full vector, bitwise across ranks.
        spec = CollectiveSpec(counts=counts)
        ar = np.asarray(_shmap1(
            mesh, lambda v, s=spec: C.allreduce(v, AXIS, spec=s))(
            jnp.asarray(xg)))
        ref = _ref_nonuniform(xg, "add")
        for r in range(p):
            np.testing.assert_allclose(
                ar[r].astype(np.float64), ref, rtol=2e-5, atol=2e-5,
                err_msg=f"counts[{name}] allreduce (p={p})")
            np.testing.assert_array_equal(ar[r], ar[0])
        n_cases += 1
        # HLO structure: ragged counts keep one collective-permute per
        # round — ceil(log2 p) for the optimal schedules (Theorem 1 /
        # Corollary 3).
        for sched in NONUNIFORM_SCHEDULES:
            spec = CollectiveSpec(schedule=sched, counts=counts)
            want = schedule_rounds(p, sched)
            n_rs = _n_collective_permutes(_shmap1(
                mesh, lambda v, s=spec: C.reduce_scatter(v, AXIS, spec=s)),
                (p, N))
            n_ar = _n_collective_permutes(_shmap1(
                mesh, lambda v, s=spec: C.allreduce(v, AXIS, spec=s)),
                (p, N))
            if sched in OPTIMAL_SCHEDULES:
                assert want == ceil_log2(p)
            assert n_rs == want, \
                (f"counts[{name}:{sched}] p={p}: {n_rs} collective-"
                 f"permutes, want {want} (Corollary 3 keeps Theorem 1's "
                 f"rounds)")
            assert n_ar == 2 * want, \
                (f"counts[{name}:{sched}] AR p={p}: {n_ar} collective-"
                 f"permutes, want {2 * want}")
            rounds[f"{name}:{sched}"] = (n_rs, n_ar)
        if verbose:
            print(f"ok: counts[{name}] p={p} (sum={N}, bmax={bmax})")
    return {"n_cases": n_cases, "rounds": rounds}


def _ref_nonuniform(xg: np.ndarray, op: str) -> np.ndarray:
    npop = _NP_OPS[op]
    red = xg[0].astype(np.float64)
    for r in range(1, xg.shape[0]):
        red = npop(red, xg[r].astype(np.float64))
    return red


# ---------------------------------------------------------------------------
# Alltoall(v) — uniform + ragged per-pair counts vs simulator + host ref
# ---------------------------------------------------------------------------

A2A_SCHEDULES = ("halving", "power2", "fully_connected")
A2A_DTYPES = ("float32", "bfloat16", "int32")


def alltoallv_counts_cases(p: int) -> dict[str, tuple[tuple[int, ...], ...]]:
    """Per-pair counts matrices for the ragged alltoallv sweep.

    ``ragged`` mixes sizes; ``zero_pairs`` has whole zero-count rows in
    the round tables (every other (src, dst) pair empty, incl. a rank
    that sends nothing); ``one_rank`` concentrates every payload on a
    single destination (the worst windowed sum — each round one rank's
    wire carries a full vector); ``uniform`` must agree with the dense
    alltoall layout.
    """
    ragged = tuple(tuple((i * 3 + j * 5 + 1) % 4 for j in range(p))
                   for i in range(p))
    zero = tuple(tuple(0 if (i + j) % 2 or i == 0 else i + j + 1
                       for j in range(p)) for i in range(p))
    one = [[0] * p for _ in range(p)]
    for i in range(p):
        one[i][p // 2] = i + 1
    return {
        "ragged": ragged,
        "zero_pairs": zero,
        "one_rank": tuple(tuple(r) for r in one),
        "uniform": tuple((BLK,) * p for _ in range(p)),
    }


def _a2a_input(case_dtype: str, shape, rng: np.random.Generator
               ) -> np.ndarray:
    if case_dtype == "int32":
        return rng.integers(-50, 50, size=shape).astype(np.int32)
    x = rng.standard_normal(shape).astype(np.float32)
    if case_dtype == "bfloat16":
        x = np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))
    return x


def run_alltoall(p: int, mesh, verbose: bool = False) -> dict:
    """Alltoall(v) conformance at axis size p.

    Uniform: circulant alltoall across schedules × dtypes × fused, each
    asserted BITWISE against the numpy simulator, the host transpose
    reference, and XLA's native all-to-all (no arithmetic happens, so
    exactness holds for every dtype), with fused == jnp bitwise.  Ragged:
    every ``alltoallv_counts_cases`` matrix across schedules, f32 + i32,
    vs ``simulate_alltoallv`` + host ref, zero rows past each rank's
    receive total.  Both forms assert the lowered-HLO collective-permute
    count == rounds(schedule) — ``ceil(log2 p)`` for halving/power2:
    ragged per-pair counts must not change the communication structure.
    """
    rng = np.random.default_rng(905 + p)
    n_cases = 0
    rounds: dict[str, tuple[int, ...]] = {}

    # --- uniform dense alltoall -------------------------------------------
    for dtype in A2A_DTYPES:
        xg = _a2a_input(dtype, (p, p, BLK), rng)
        dt = jnp.dtype(dtype)
        ref = sim.ref_alltoall(
            [[xg[r, i] for i in range(p)] for r in range(p)])
        W, stats = sim.simulate_alltoall(
            [[xg[r, i] for i in range(p)] for r in range(p)])
        assert stats.rounds == ceil_log2(p)
        for sched in A2A_SCHEDULES:
            spec = CollectiveSpec(schedule=sched)
            outs = {}
            for fused in (False, True):
                s = spec.with_(use_fused_kernel=fused)
                out = np.asarray(_shmap1(
                    mesh, lambda v, s=s: C.alltoall(v, AXIS, spec=s),
                    check_vma=False if fused else None)(
                    jnp.asarray(xg, dtype=dt)))
                outs[fused] = out
                for r in range(p):
                    for j in range(p):
                        np.testing.assert_array_equal(
                            out[r, j],
                            np.asarray(W[r][j]).astype(out.dtype),
                            err_msg=f"alltoall[{sched}:{dtype}"
                                    f"{':fused' if fused else ''}] vs "
                                    f"simulator (p={p}, rank {r})")
                        np.testing.assert_array_equal(
                            out[r, j],
                            np.asarray(ref[r][j]).astype(out.dtype),
                            err_msg=f"alltoall[{sched}:{dtype}] vs host "
                                    f"ref (p={p})")
                n_cases += 1
            np.testing.assert_array_equal(
                outs[True], outs[False],
                err_msg=f"alltoall[{sched}:{dtype}] fused != jnp (p={p})")
        # XLA native baseline (layout contract identical).
        base = np.asarray(_shmap1(
            mesh, lambda v: C.alltoall(
                v, AXIS, spec=CollectiveSpec(kind="xla")))(
            jnp.asarray(xg, dtype=dt)))
        np.testing.assert_array_equal(
            base, outs[False],
            err_msg=f"alltoall[{dtype}] circulant != xla baseline (p={p})")
        n_cases += 1

    # HLO structure (uniform): one collective-permute per round, fused too.
    for sched in A2A_SCHEDULES:
        spec = CollectiveSpec(schedule=sched)
        want = schedule_rounds(p, sched)
        if sched in OPTIMAL_SCHEDULES:
            assert want == ceil_log2(p)
        got = []
        for fused in (False, True):
            s = spec.with_(use_fused_kernel=fused)
            jitted = _shmap1(mesh, lambda v, s=s: C.alltoall(v, AXIS, spec=s),
                             check_vma=False if fused else None)
            n_cp = _n_collective_permutes(jitted, (p, p, BLK))
            assert n_cp == want, \
                (f"alltoall[{sched}{':fused' if fused else ''}] p={p}: "
                 f"{n_cp} collective-permutes, want {want} (Theorem 1's "
                 f"rounds; ceil(log2 p) for the optimal schedules)")
            got.append(n_cp)
        rounds[f"uniform:{sched}"] = tuple(got)

    # --- ragged alltoallv -------------------------------------------------
    for name, counts in alltoallv_counts_cases(p).items():
        send_tot = [sum(row) for row in counts]
        recv_tot = [sum(counts[s][d] for s in range(p)) for d in range(p)]
        in_h = max(max(send_tot), 1)
        for dtype in ("float32", "int32"):
            inputs = [[_a2a_input(dtype, (counts[r][d], 2), rng)
                       for d in range(p)] for r in range(p)]
            xg = np.zeros((p, in_h, 2),
                          np.int32 if dtype == "int32" else np.float32)
            for r in range(p):
                j = 0
                for d in range(p):
                    c = counts[r][d]
                    xg[r, j:j + c] = inputs[r][d]
                    j += c
            W, stats = sim.simulate_alltoallv(inputs)
            ref = sim.ref_alltoall(inputs)
            for sched in A2A_SCHEDULES:
                spec = CollectiveSpec(schedule=sched, counts=counts)
                tag = f"alltoallv[{name}:{sched}:{dtype}]"
                out = np.asarray(_shmap1(
                    mesh, lambda v, s=spec: C.alltoall(v, AXIS, spec=s))(
                    jnp.asarray(xg)))
                for r in range(p):
                    j = 0
                    for s_ in range(p):
                        c = counts[s_][r]
                        np.testing.assert_array_equal(
                            out[r, j:j + c], np.asarray(W[r][s_], out.dtype),
                            err_msg=f"{tag} vs simulator (p={p}, rank {r})")
                        np.testing.assert_array_equal(
                            out[r, j:j + c],
                            np.asarray(ref[r][s_], out.dtype),
                            err_msg=f"{tag} vs host ref (p={p}, rank {r})")
                        j += c
                    assert j == recv_tot[r]
                    assert (out[r, j:] == 0).all(), \
                        f"{tag}: rows past recv total must be zero (p={p})"
                n_cases += 1
        # HLO structure: ragged counts keep one collective-permute per
        # round (= ceil(log2 p) for the optimal schedules).
        for sched in A2A_SCHEDULES:
            spec = CollectiveSpec(schedule=sched, counts=counts)
            want = schedule_rounds(p, sched)
            n_cp = _n_collective_permutes(_shmap1(
                mesh, lambda v, s=spec: C.alltoall(v, AXIS, spec=s)),
                (p, in_h))
            assert n_cp == want, \
                (f"alltoallv[{name}:{sched}] p={p}: {n_cp} collective-"
                 f"permutes, want {want} (ragged per-pair counts must not "
                 f"change the round structure)")
            rounds[f"{name}:{sched}"] = (n_cp,)
        if verbose:
            print(f"ok: alltoallv[{name}] p={p} "
                  f"(total={sum(send_tot)} rows)")
    if verbose:
        print(f"ok: alltoall sweep p={p} ({n_cases} cases)")
    return {"n_cases": n_cases, "rounds": rounds}


# ---------------------------------------------------------------------------
# Hierarchical (multi-axis) sweep — nested RS/AG/AR over a 2-D mesh
# ---------------------------------------------------------------------------

def hierarchical_factors(p: int) -> tuple[int, int] | None:
    """(p // g, g) mesh factorization for the two-axis sweep; None for
    primes (no non-trivial 2-D mesh exists)."""
    g = two_level_group(p)
    if g <= 1:
        return None
    return (p // g, g)


def _shmap2(mesh, fn, check_vma: bool | None = None):
    """Per-rank fn over a (p, ...) global sharded on dim 0 across BOTH
    mesh axes ('x'-major rank order — the layout the nested hierarchical
    collectives produce)."""
    return jax.jit(compat.shard_map(
        lambda v: fn(v[0])[None], mesh=mesh,
        in_specs=(P(("x", "y")),), out_specs=P(("x", "y")),
        check_vma=check_vma))


def run_hierarchical(p: int, verbose: bool = False) -> dict | None:
    """Two-axis conformance: hierarchical_reduce_scatter / allgather /
    allreduce over a (p//g, g) mesh vs the host reference, on the jnp and
    fused paths, uncompressed and int8-wire; plus HLO collective-permute
    counts (= sum of the per-axis round counts).  Returns None for prime
    p (no 2-D factorization)."""
    fac = hierarchical_factors(p)
    if fac is None:
        return None
    a, b = fac
    mesh = compat.make_mesh((a, b), ("x", "y"))
    axes = ("x", "y")
    rng = np.random.default_rng(977 + p)
    n = p * BLK
    xg = rng.standard_normal((p, n)).astype(np.float32)
    ref = xg.astype(np.float64).sum(axis=0)
    ref_blocks = ref.reshape(p, BLK)
    blocks = rng.standard_normal((p, BLK)).astype(np.float32)
    n_cases = 0
    rounds_want = ceil_log2(a) + ceil_log2(b)
    results: dict[str, tuple[int, int]] = {}
    for fused in (False, True):
        cv = False if fused else None
        for wire in (None, "int8"):
            kw = {"use_fused_kernel": fused}
            if wire:
                kw["wire_dtype"] = wire
            tol = ({"rtol": 2e-5, "atol": 2e-5} if wire is None
                   else {"rtol": 0.1, "atol": 0.05 * p + 0.1})
            tag = f"{a}x{b}" + (":fused" if fused else "") + \
                (":w8" if wire else "")
            # RS over ('x', 'y'): rank (rx, ry) ends with linear block
            # rx*b + ry — exactly the P(('x', 'y')) rank order.
            out = np.asarray(_shmap2(
                mesh, lambda v: C.hierarchical_reduce_scatter(
                    v, axes, **kw), cv)(jnp.asarray(xg)))
            for rr in range(p):
                np.testing.assert_allclose(
                    out[rr].astype(np.float64), ref_blocks[rr], **tol,
                    err_msg=f"hierarchical RS[{tag}] p={p}")
            # AG inverts RS's layout: every rank reassembles the blocks
            # in linear rank order, replicated.
            ag = np.asarray(_shmap2(
                mesh, lambda v: C.hierarchical_allgather(v, axes, **kw),
                cv)(jnp.asarray(blocks)))
            ag_tol = ({"rtol": 0, "atol": 0} if wire is None
                      else {"rtol": 0.02, "atol": 0.05})
            for rr in range(p):
                np.testing.assert_allclose(
                    ag[rr].reshape(p, BLK).astype(np.float64),
                    blocks.astype(np.float64), **ag_tol,
                    err_msg=f"hierarchical AG[{tag}] p={p}")
            # AR: replicated full reduce (bitwise-replicated even on the
            # wire path — all ranks dequantize identical codes).
            ar = np.asarray(_shmap2(
                mesh, lambda v: C.hierarchical_allreduce(v, axes, **kw),
                cv)(jnp.asarray(xg)))
            for rr in range(p):
                np.testing.assert_allclose(
                    ar[rr].astype(np.float64), ref, **tol,
                    err_msg=f"hierarchical AR[{tag}] p={p}")
                np.testing.assert_array_equal(ar[rr], ar[0])
            n_cases += 3
            # HLO structure: nested rounds = sum over axes (Theorem 1/2
            # per axis).
            n_rs = _n_collective_permutes(
                _shmap2(mesh, lambda v: C.hierarchical_reduce_scatter(
                    v, axes, **kw), cv), (p, n))
            n_ar = _n_collective_permutes(
                _shmap2(mesh, lambda v: C.hierarchical_allreduce(
                    v, axes, **kw), cv), (p, n))
            assert n_rs == rounds_want, \
                (f"hierarchical RS[{tag}] p={p}: {n_rs} collective-"
                 f"permutes, want {rounds_want}")
            assert n_ar == 2 * rounds_want, \
                (f"hierarchical AR[{tag}] p={p}: {n_ar} collective-"
                 f"permutes, want {2 * rounds_want}")
            results[tag] = (n_rs, n_ar)
            if verbose:
                print(f"ok: hierarchical[{tag}] p={p} RS/AG/AR "
                      f"(rounds {n_rs}/{n_ar})")
    return {"mesh": (a, b), "n_cases": n_cases, "rounds": results}


# ---------------------------------------------------------------------------
# Elastic re-plan conformance (device-free)
# ---------------------------------------------------------------------------

def run_elastic_replan(p: int, verbose: bool = False) -> dict:
    """Every uniform sweep spec must re-plan cleanly at resized worlds —
    shrink, grow, and odd p' (the any-p property the elastic controller
    leans on) — passing the same static verifier ``build_zero1`` runs as
    pre-flight, and selective invalidation of the old world's cache
    entries must not disturb the fresh plans.  Pure schedule work: no
    devices, microseconds per (spec, p').
    """
    from repro.analysis.verify import assert_verified
    from repro.core.plan import plan

    specs = []
    for case in sweep_cases(p):
        sp = case_spec(case, p)
        # counts/group are sized for THIS p — an elastic re-plan carries
        # the SAME spec to a new world, so only world-free specs apply
        # (grad-sync specs are exactly this shape).
        if sp.counts is None and sp.group is None and sp not in specs:
            specs.append(sp)
    worlds = sorted({w for w in (max(2, p - 1), p + 1, 3, 2 * p)
                     if w != p})
    n_replans = 0
    for sp in specs:
        plan(sp, p=p, axis_name=AXIS)  # the "old world" entry
        for p2 in worlds:
            assert_verified(plan(sp, p=p2, axis_name=AXIS))
            n_replans += 1
        evicted = plan.invalidate(p=p, axis_name=AXIS)
        assert evicted >= 1, f"{sp}: old-world plan not evicted"
        for p2 in worlds:  # fresh plans survive the selective eviction
            assert plan(sp, p=p2, axis_name=AXIS) is \
                plan(sp, p=p2, axis_name=AXIS), \
                f"{sp}: p'={p2} plan lost cache identity after invalidate"
        # rebuilding the evicted world must verify again (p -> p' -> p)
        assert_verified(plan(sp, p=p, axis_name=AXIS))
    if verbose:
        print(f"ok: elastic re-plan p={p} -> p'={worlds}: "
              f"{len(specs)} specs x {len(worlds)} worlds verified, "
              f"selective eviction clean")
    return {"n_specs": len(specs), "worlds": worlds,
            "n_replans": n_replans}


# ---------------------------------------------------------------------------
# Broadcast plan kind (Träff, arXiv:2407.18004) — all-broadcast
# ---------------------------------------------------------------------------

BROADCAST_SCHEDULES = OPTIMAL_SCHEDULES + ("fully_connected",)


def run_broadcast(p: int, mesh, verbose: bool = False) -> dict:
    """``kind="broadcast"`` conformance: numeric exactly-once delivery
    and HLO round counts.

    Per schedule × dtype: every rank contributes a (BLK, 2) block; the
    gathered (p*BLK, 2) output must hold rank j's block at row-block j,
    BITWISE, and be replicated across ranks (payloads move uncompressed
    — weight fan-out must be bit-exact).  The lowered HLO must contain
    exactly one collective-permute per schedule round — ceil(log2 p)
    for halving/power2, the broadcast paper's lower bound at any p.
    """
    from repro.analysis.verify import assert_verified
    from repro.core.plan import plan
    rng = np.random.default_rng(777 + p)
    n_cases = 0
    rounds: dict[str, int] = {}
    for sched in BROADCAST_SCHEDULES:
        spec = CollectiveSpec(kind="broadcast", schedule=sched)
        assert_verified(plan(spec, p=p, axis_name=AXIS))
        fn = lambda v, spec=spec: C.broadcast(v, AXIS, spec=spec)
        for dtype in ("float32", "int32"):
            xg = (rng.standard_normal((p, BLK, 2)).astype(dtype)
                  if dtype == "float32" else
                  rng.integers(-50, 50, (p, BLK, 2)).astype(dtype))
            out = np.asarray(_shmap1(mesh, fn)(jnp.asarray(xg)))
            want = xg.reshape(p * BLK, 2)
            for r in range(p):
                np.testing.assert_array_equal(
                    out[r].reshape(p * BLK, 2), want,
                    err_msg=f"broadcast[{sched}:{dtype}] p={p} rank {r}")
            n_cases += 1
        want_rounds = schedule_rounds(p, sched)
        if sched in OPTIMAL_SCHEDULES:
            assert want_rounds == ceil_log2(p)
        n_cp = count_collective_permutes(mesh, p, fn)
        assert n_cp == want_rounds, \
            (f"broadcast[{sched}] p={p}: {n_cp} collective-permutes, "
             f"want {want_rounds} (one ppermute per round)")
        rounds[sched] = n_cp
        if verbose:
            print(f"ok: broadcast[{sched}] p={p}: bitwise all-delivery, "
                  f"HLO cp={n_cp} (ceil_log2={ceil_log2(p)})")
    return {"n_cases": n_cases, "rounds": rounds}


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def run_sweep(p: int, mesh=None, verbose: bool = False) -> dict:
    """Full conformance sweep at axis size p (requires >= p devices)."""
    if mesh is None:
        mesh = compat.make_mesh((p,), (AXIS,))
    rng = np.random.default_rng(1234 + p)
    cases = sweep_cases(p)
    for case in cases:
        run_case(mesh, p, case, rng)
        if verbose:
            print(f"ok: {case.label}")
    rounds = check_round_counts(mesh, p)
    if verbose:
        for sched, (n_rs, n_ar) in rounds.items():
            print(f"ok: HLO rounds p={p} {sched}: RS={n_rs} AR={n_ar} "
                  f"(ceil_log2={ceil_log2(p)})")
    nonuni = run_nonuniform(p, mesh, verbose=verbose)
    a2a = run_alltoall(p, mesh, verbose=verbose)
    bcast = run_broadcast(p, mesh, verbose=verbose)
    hier = run_hierarchical(p, verbose=verbose)
    elastic = run_elastic_replan(p, verbose=verbose)
    return {"p": p, "n_cases": len(cases), "rounds": rounds,
            "nonuniform": nonuni, "alltoall": a2a, "broadcast": bcast,
            "hierarchical": hier, "elastic": elastic}


def main(argv=None) -> int:
    """CLI: run the full conformance matrix at ``argv[0]`` ranks
    (default 8) on fake devices; exit 0 iff every case passes."""
    argv = argv if argv is not None else sys.argv[1:]
    p = int(argv[0]) if argv else 8
    if jax.device_count() < p:
        print(f"need {p} devices, have {jax.device_count()} "
              f"(set XLA_FLAGS=--xla_force_host_platform_device_count={p})")
        return 2
    report = run_sweep(p, verbose=True)
    hier = report.get("hierarchical")
    hier_note = (f", hierarchical {hier['mesh'][0]}x{hier['mesh'][1]}: "
                 f"{hier['n_cases']} cases" if hier else "")
    nonuni = report["nonuniform"]
    a2a = report["alltoall"]
    bcast = report["broadcast"]
    el = report["elastic"]
    print(f"CONFORMANCE OK (p={p}, {report['n_cases']} cases, "
          f"{len(report['rounds'])} schedules, "
          f"{nonuni['n_cases']} non-uniform cases, "
          f"{a2a['n_cases']} alltoall cases, "
          f"{bcast['n_cases']} broadcast cases, "
          f"{el['n_replans']} elastic re-plans{hier_note})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
