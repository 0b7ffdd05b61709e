"""Cost-model-guided block-shape / engine / strategy selection.

The right (engine, bi, bj, bm, bn) for the bulk comparison kernels
depends on the machine: interpret mode on CPU wants few, cache-sized
grid steps; a real TPU wants every working set inside VMEM and, for
narrow §4 windows, the MXU thermometer engine whose FLOPs scale with
the value span.  Since PR 7 the search is two-stage:

1. **Analytic cost model** (``predict_cost``): per candidate, a
   VMEM-fit check (the same ``template.vmem_estimate`` the kernel
   generator refuses over-budget specs with) plus an order-of-magnitude
   time estimate from HBM traffic, compute work (VPU element ops or MXU
   FLOPs with utilization), and per-grid-step overhead.  Candidates are
   RANKED by predicted time and only the top half survive — the model
   prunes, it never has the final word.
2. **Measured ranking**: survivors race on the live backend; the
   fastest wins the table entry.

Winners are cached in a JSON table keyed by

    op | backend | N-bucket | M-bucket | m-bucket | s<shards>

(shape buckets are powers of two, rounded up, so one sweep covers a
band of nearby shapes; the shard count is part of the key, so a 2-shard
tune can never poison the 1-shard entry for the same global shape).
``repro.causal.CausalEngine`` consults ``lookup`` when it resolves a
call's engine and blocks, and falls back to conservative per-backend
defaults when the table has no entry.

The ``matrix_sharded`` op also records a per-shape **strategy**
decision — ``ring`` (halved ppermute block-row sweep) vs ``replicated``
(gather the slab once, run the single-device triangle engine) — which
the engine dispatches the sharded all-pairs sweep on.  The cost model
knows that forced-host device meshes serialize onto the host cores
(ring collectives buy no parallelism there), so CI backends predict
``replicated`` while a real multi-core mesh predicts ``ring``.

Regenerate the shipped table with

    PYTHONPATH=src python -m repro.kernels.autotune --write

which sweeps the standard shapes on the current machine and rewrites
``autotune_table.json`` next to this module (or ``--out PATH`` /
``$REPRO_AUTOTUNE_TABLE`` for a private table).  ``--explain`` prints,
per (op, shape bucket), the cost model's predicted ranking next to the
measured result so the pruning quality is auditable; ``--trace-dir``
attaches a ``repro.obs`` Observer that records one span per sweep and
search counters (candidates / pruned / measured).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import time
from pathlib import Path

import jax
from jax._src.pallas.mosaic.error_handling import MosaicError

from repro.kernels import ops
from repro.kernels.template import resolve_interpret

__all__ = [
    "lookup",
    "key_for",
    "predict_cost",
    "predict_hybrid_cost",
    "predict_sharded_cost",
    "prune",
    "autotune_matrix",
    "autotune_matrix_sharded",
    "autotune_one_vs_many",
    "autotune_hybrid",
    "autotune_shapes",
    "table_path",
    "load_table",
    "save_table",
    "vmem_bytes",
    "SEARCH_STATS",
]

_DEFAULT_TABLE = Path(__file__).parent / "autotune_table.json"
_ENV = "REPRO_AUTOTUNE_TABLE"

_table_cache: dict | None = None
_table_cache_path: str | None = None


def table_path() -> Path:
    return Path(os.environ.get(_ENV, _DEFAULT_TABLE))


def load_table() -> dict:
    global _table_cache, _table_cache_path
    path = table_path()
    if _table_cache is not None and _table_cache_path == str(path):
        return _table_cache
    try:
        with open(path) as f:
            _table_cache = json.load(f)
    except (OSError, ValueError):
        _table_cache = {}
    _table_cache_path = str(path)
    return _table_cache


def save_table(table: dict, path: Path | None = None) -> Path:
    global _table_cache, _table_cache_path
    path = path or table_path()
    with open(path, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
        f.write("\n")
    _table_cache, _table_cache_path = table, str(path)
    return path


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _backend(interpret: bool) -> str:
    return "interpret" if interpret else "tpu"


def key_for(op: str, N: int, M: int, m: int, interpret: bool,
            shards: int = 1) -> str:
    """Table key.  The shard count is explicit: block resolution for a
    d-shard ring differs from the 1-shard sweep of the SAME global
    shape, so their entries must never alias."""
    return (f"{op}|{_backend(interpret)}|N{_bucket(N)}|M{_bucket(M)}"
            f"|m{_bucket(m)}|s{shards}")


# running tallies for the two-stage search (the obs layer / CLI
# snapshot deltas around sweeps)
SEARCH_STATS = {"candidates": 0, "pruned": 0, "measured": 0}


def lookup(op: str, N: int, M: int, m: int, interpret: bool,
           shards: int = 1) -> dict | None:
    """Best known config for this op/shape/shard band, or None."""
    return load_table().get(key_for(op, N, M, m, interpret, shards))


# ---------------------------------------------------------------------------
# analytic cost model
# ---------------------------------------------------------------------------

def vmem_bytes(engine: str, bi: int, bj: int, bm: int,
               n_thresholds: int = 0) -> int:
    """Peak per-step working set of one grid step of a matrix engine.

    Delegates to the kernel generator's estimate (``template
    .vmem_estimate``) at pipeline depth 1, so the search space and the
    generator refuse the same over-budget combos from ONE model."""
    from repro.kernels.template import CompareSpec, vmem_estimate
    spec = {
        "tri": lambda: CompareSpec(topology="tri", pack="u8", bi=bi, bj=bi,
                                   bm=bm, pipeline_depth=1),
        "full": lambda: CompareSpec(topology="rect", pack="u8", bi=bi, bj=bj,
                                    bm=bm, pipeline_depth=1),
        "i32": lambda: CompareSpec(topology="rect", pack="i32", bi=bi, bj=bj,
                                   bm=bm, with_stats=True, pipeline_depth=1),
        "mxu": lambda: CompareSpec(topology="mxu", pack="u8", bi=bi, bj=bj,
                                   bm=bm, with_base=True, pipeline_depth=1,
                                   n_thresholds=max(n_thresholds, 1)),
    }.get(engine)
    if spec is None:
        raise ValueError(engine)
    return vmem_estimate(spec())


def _fits(engine: str, bi: int, bj: int, bm: int, interpret: bool,
          n_thresholds: int = 0) -> bool:
    from repro.kernels.template import VMEM_BUDGET
    return vmem_bytes(engine, bi, bj, bm, n_thresholds) <= \
        VMEM_BUDGET[_backend(interpret)]


# Order-of-magnitude machine constants.  Only the RANKING matters (the
# model prunes, measurement decides), so these are deliberately coarse:
#   interpret — a Python-dispatched emulation: per-grid-step overhead in
#       the milliseconds dominates; elementwise work runs at numpy-ish
#       rates and dot_general ~10x denser than elementwise loops.
#   tpu — per-step cost is the roofline max of HBM streaming and
#       compute; grid-step overhead is microseconds.
_MODEL = {
    "interpret": dict(step_overhead=2.0e-3, elem=4.0e-10, mxu_flop=4.0e-11,
                      hbm=0.0),
    "tpu": dict(step_overhead=2.0e-6, elem=5.0e-13, mxu_flop=2.2e-15,
                hbm=1.25e-12),
}


def predict_cost(engine: str, N: int, M: int, m: int,
                 bi: int, bj: int, bm: int, interpret: bool,
                 n_thresholds: int = 0) -> float:
    """Predicted seconds for one all-pairs sweep with this candidate.

    Infinite when the per-step working set busts the VMEM budget — the
    model and the kernel generator refuse the same combos."""
    if not _fits(engine, bi, bj, bm, interpret, n_thresholds):
        return math.inf
    c = _MODEL[_backend(interpret)]
    gi, gj, gm = -(-N // bi), -(-M // bj), -(-m // bm)
    pairs = gi * (gi + 1) // 2 if engine == "tri" else gi * gj
    steps = pairs * gm
    elem_per_step = bi * bj * bm * (2 if engine == "i32" else 1)
    if engine == "mxu":
        # thermometer encodes elementwise, then one MXU contraction;
        # utilization falls off for sub-128 tiles
        util = min(bi, 128) * min(bj, 128) / (128 * 128)
        compute = steps * ((bi + bj) * bm * n_thresholds * c["elem"]
                           + 2 * bi * bj * bm * n_thresholds
                           * c["mxu_flop"] / max(util, 1e-3))
    else:
        compute = steps * elem_per_step * c["elem"]
    esize = 4 if engine == "i32" else 1
    hbm = steps * (bi + bj) * bm * esize * c["hbm"]
    return steps * c["step_overhead"] + max(compute, hbm)


def predict_hybrid_cost(N: int, H: int, m: int, bn: int, bm: int,
                        interpret: bool) -> float:
    """Predicted seconds for one fused hot+tail hybrid classify.

    Hot row-tiles run only the integer containment branch (their tail
    operand index stays on block 0, so nothing new is fetched): they
    cost the per-step overhead.  Tail row-tiles stream and compare a
    packed tile.  The hybrid speedup the bench demonstrates comes from
    the smaller tail geometry ``m`` an fp budget allows once the
    fp-binding hot sessions are carried exactly — which this model sees
    through ``m``.  ``N`` is the TOTAL row count, ``H`` of which are
    hot."""
    c = _MODEL[_backend(interpret)]
    T = max(N - H, 1)
    mtiles = -(-m // bm)
    hot_steps = -(-H // bn) * mtiles
    tail_steps = -(-T // bn) * mtiles
    return (hot_steps * c["step_overhead"]
            + tail_steps * (c["step_overhead"]
                            + bn * bm * (c["elem"] + c["hbm"])))


def _host_serialized(interpret: bool) -> bool:
    """True when mesh devices are forced host-platform devices sharing
    the physical cores — collectives there buy zero parallel compute
    (the CI topology: XLA_FLAGS=--xla_force_host_platform_device_count)."""
    return interpret or jax.default_backend() == "cpu"


def predict_sharded_cost(strategy: str, N: int, m: int, shards: int,
                         interpret: bool, *, bi: int | None = None,
                         bj: int | None = None, bm: int = 512) -> float:
    """Predicted seconds for one sharded all-pairs sweep.

    ``ring``: every device sweeps its [N/d, m] block-row — the tri
    diagonal plus halved visiting offsets — so TOTAL work matches the
    single-device triangle; wall-clock divides by d only when devices
    are physically parallel, and each of the 1 + d//2 steps pays a
    collective overhead.  ``replicated``: one gather of the u8 slab,
    then the plain single-device triangle sweep."""
    if shards == 1:
        strategy = "replicated"          # a 1-wide ring is the plain sweep
    if bi is None or bj is None:
        # the defaults CausalEngine.blocks falls back to on every backend
        bi = bj = 128
    tri = predict_cost("tri", N, N, m, bi, bj, bm, interpret)
    if strategy == "replicated":
        gather = N * m * _MODEL[_backend(interpret)].get("hbm", 0.0) or \
            N * m * 1e-9 * (1.0 if _host_serialized(interpret) else 0.1)
        return tri + gather
    if strategy != "ring":
        raise ValueError(strategy)
    parallel = 1.0 if _host_serialized(interpret) else float(shards)
    steps = 1 + shards // 2
    collective = steps * (2.0e-3 if _host_serialized(interpret) else 5.0e-6)
    # ship-backs and per-step dispatch also serialize on a shared host
    ring_overhead = steps * shards * \
        (1.0e-3 if _host_serialized(interpret) else 0.0)
    return tri / parallel + collective + ring_overhead


def prune(candidates: list, predicted: list[float]) -> list:
    """Keep at most half of ``candidates`` (capped at 8) ranked by
    predicted cost — always at least one; infinite predictions (VMEM
    busts) never survive."""
    if not candidates:
        return []
    order = sorted(range(len(candidates)), key=lambda i: predicted[i])
    keep = max(1, min(len(candidates) // 2, 8))
    kept = [candidates[i] for i in order[:keep]
            if predicted[i] < math.inf]
    SEARCH_STATS["candidates"] += len(candidates)
    SEARCH_STATS["pruned"] += len(candidates) - len(kept)
    return kept or [candidates[order[0]]]


# ---------------------------------------------------------------------------
# measured sweeps
# ---------------------------------------------------------------------------

# What a candidate the backend cannot build raises, and nothing else:
# the generator's VMEM refusal and Pallas block checks (ValueError), an
# op with no lowering (NotImplementedError), and the chip compiler's
# refusals (MosaicError, or a runtime error when VMEM runs out).
CANDIDATE_REFUSED = (ValueError, NotImplementedError,
                     jax.errors.JaxRuntimeError, MosaicError)


def _divisor_blocks(size: int, want: tuple, mult: int) -> list:
    return [b for b in want if b % mult == 0 and b <= size and size % b == 0]


def _measure(fn, reps: int = 3) -> float:
    jax.block_until_ready(jax.tree.leaves(fn()))     # warm / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(jax.tree.leaves(fn()))
        best = min(best, time.perf_counter() - t0)
    SEARCH_STATS["measured"] += 1
    return best


def _rand_packed(N: int, m: int, span: int, seed: int = 0):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    cells = jnp.asarray(rng.integers(0, span, (N, m)), jnp.uint8)
    base = jnp.zeros((N, 1), jnp.int32)
    return cells, base


def _matrix_candidates(N: int, m: int, span: int, interpret: bool) -> list:
    """The full knob grid for the matrix op (before the model prunes)."""
    out = []
    for bi in (8, 64, 128, 256):
        for bm in (128, 256, 512, 1024):
            if not (_divisor_blocks(N, (bi,), 8)
                    and _divisor_blocks(m, (bm,), 128)):
                continue
            out.append(("tri", bi, bi, bm))
            out.append(("i32", bi, bi, bm))
            if span <= ops.MXU_SPAN_MAX:
                out.append(("mxu", bi, bi, bm))
    return out


def autotune_matrix(N: int, m: int, *, span: int = 30,
                    interpret: bool | None = None, verbose: bool = False,
                    explain: dict | None = None):
    """Race matrix engines x block shapes at [N, m]; return best config.

    The analytic model ranks the full grid first and only the top half
    is measured.  Pass ``explain={}`` to receive the predicted ranking,
    the survivor list, and the measured times for auditing."""
    interpret = resolve_interpret(interpret)
    cells, base = _rand_packed(N, m, span)
    cells_i32 = cells.astype("int32")

    grid = _matrix_candidates(N, m, span, interpret)
    predicted = [predict_cost(e, N, N, m, bi, bj, bm, interpret,
                              n_thresholds=span if e == "mxu" else 0)
                 for (e, bi, bj, bm) in grid]
    survivors = prune(grid, predicted)
    if explain is not None:
        ranking = sorted(zip(grid, predicted), key=lambda t: t[1])
        explain["grid"] = len(grid)
        explain["predicted"] = [
            {"engine": e, "bi": bi, "bj": bj, "bm": bm, "pred_us": p * 1e6}
            for (e, bi, bj, bm), p in ranking]
        explain["survivors"] = len(survivors)

    results = []
    for engine, bi, bj, bm in survivors:
        try:
            if engine == "i32":
                fn = lambda: ops.compare_i32(
                    cells_i32, cells_i32, bi=bi, bj=bj, bm=bm,
                    interpret=interpret)
            else:
                bounds = (ops.logical_bounds(cells, base, cells, base)
                          if engine == "mxu" else None)
                fn = lambda: ops.compare_packed(
                    cells, base, engine=engine, bi=bi, bj=bj, bm=bm,
                    uniform_base=True, interpret=interpret, bounds=bounds)
            dt = _measure(fn)
        except CANDIDATE_REFUSED as e:    # candidate invalid on this backend
            if verbose:
                print(f"  matrix {engine} bi={bi} bm={bm}: FAILED {e}")
            continue
        results.append({"engine": engine, "bi": bi, "bj": bj, "bm": bm,
                        "us": dt * 1e6})
        if verbose:
            print(f"  matrix {engine} bi={bi} bj={bj} bm={bm}: {dt*1e3:.1f} ms")
    if not results:
        raise RuntimeError(f"no viable matrix candidates for N={N} m={m}")
    if explain is not None:
        explain["measured"] = sorted(results, key=lambda r: r["us"])
    return min(results, key=lambda r: r["us"])


def autotune_matrix_sharded(N: int, m: int, shards: int, *, span: int = 30,
                            interpret: bool | None = None,
                            verbose: bool = False,
                            explain: dict | None = None):
    """Race ring vs replicated for the sharded symmetric all-pairs sweep.

    Returns {"strategy", "bi", "bj", "bm", "us"} — the config the
    engine's sharded all-pairs sweep dispatches on.  Both strategies
    run at the ``matrix`` entry's blocks (the defaults without one)."""
    interpret = resolve_interpret(interpret)
    from repro.launch.mesh import make_fleet_mesh

    if len(jax.devices()) < shards:
        raise RuntimeError(
            f"{shards}-shard sweep needs {shards} devices, "
            f"have {len(jax.devices())}")
    mesh = make_fleet_mesh(shards)
    cells, base = _rand_packed(N, m, span)
    blocks = lookup("matrix", N, N, m, interpret) or {}
    bi = blocks.get("bi", 128)
    bj = blocks.get("bj", 128)
    bm = blocks.get("bm", 512)

    grid = ["ring", "replicated"]
    predicted = [predict_sharded_cost(s, N, m, shards, interpret,
                                      bi=bi, bj=bj, bm=bm) for s in grid]
    if explain is not None:
        ranking = sorted(zip(grid, predicted), key=lambda t: t[1])
        explain["predicted"] = [
            {"strategy": s, "pred_us": p * 1e6} for s, p in ranking]

    results = []
    kw = dict(mesh=mesh, axis="fleet", bi=bi, bj=bj, bm=bm,
              uniform_base=True, interpret=interpret)
    for strategy in grid:
        try:
            if strategy == "ring":
                fn = lambda: ops.compare_ring(cells, base, **kw)
            else:
                fn = lambda: ops.compare_replicated(
                    cells, base, engine="tri", mesh_outputs=True, **kw)
            dt = _measure(fn)
        except CANDIDATE_REFUSED as e:
            if verbose:
                print(f"  matrix_sharded {strategy} d={shards}: FAILED {e}")
            continue
        results.append({"strategy": strategy, "bi": bi, "bj": bj, "bm": bm,
                        "us": dt * 1e6})
        if verbose:
            print(f"  matrix_sharded {strategy} d={shards}: {dt*1e3:.1f} ms")
    if not results:
        raise RuntimeError(
            f"no viable sharded candidates for N={N} m={m} d={shards}")
    if explain is not None:
        explain["measured"] = sorted(results, key=lambda r: r["us"])
    return min(results, key=lambda r: r["us"])


def autotune_one_vs_many(N: int, m: int, *, span: int = 30,
                         interpret: bool | None = None,
                         verbose: bool = False,
                         explain: dict | None = None):
    import jax.numpy as jnp

    interpret = resolve_interpret(interpret)
    cells, base = _rand_packed(N, m, span)
    q = cells[0].astype(jnp.int32)

    grid = []
    for bn in (8, 32, 128, 256):
        for bm in (128, 256, 512, 1024):
            if (_divisor_blocks(N, (bn,), 8)
                    and _divisor_blocks(m, (bm,), 128)):
                grid.append((bn, bm))
    # one-vs-many is O(N * m) total: per-step overhead dominates, so the
    # model is simply step count x overhead + streamed work
    c = _MODEL[_backend(interpret)]
    predicted = [(-(-N // bn)) * (-(-m // bm))
                 * (c["step_overhead"] + bn * bm * c["elem"])
                 for (bn, bm) in grid]
    survivors = prune(grid, predicted)
    if explain is not None:
        ranking = sorted(zip(grid, predicted), key=lambda t: t[1])
        explain["grid"] = len(grid)
        explain["predicted"] = [
            {"engine": "packed", "bn": bn, "bm": bm, "pred_us": p * 1e6}
            for (bn, bm), p in ranking]
        explain["survivors"] = len(survivors)

    results = []
    for bn, bm in survivors:
        try:
            dt = _measure(lambda: ops.classify_packed(
                q, cells, base, bn=bn, bm=bm, interpret=interpret))
        except CANDIDATE_REFUSED:
            continue
        results.append({"engine": "packed", "bn": bn, "bm": bm,
                        "us": dt * 1e6})
        if verbose:
            print(f"  one_vs_many bn={bn} bm={bm}: {dt*1e3:.2f} ms")
    if not results:
        raise RuntimeError(f"no viable one_vs_many candidates N={N} m={m}")
    if explain is not None:
        explain["measured"] = sorted(results, key=lambda r: r["us"])
    return min(results, key=lambda r: r["us"])


def autotune_hybrid(N: int, m: int, *, hot: int | None = None,
                    span: int = 30, interpret: bool | None = None,
                    verbose: bool = False, explain: dict | None = None):
    """Race block shapes for the fused hot+tail hybrid classify.

    ``N`` is the TOTAL row count; ``hot`` (default N // 8) of those are
    exact hot rows, the rest the packed bloom tail.  Winners land under
    ``key_for("hybrid", N, hot, m, ...)`` — the hot count rides in the
    M slot — matching the ``CausalEngine.blocks`` lookup."""
    import jax.numpy as jnp
    import numpy as np

    interpret = resolve_interpret(interpret)
    hot = hot if hot is not None else max(8, N // 8)
    T = max(8, N - hot)
    cells, base = _rand_packed(T, m, span)
    q = cells[0].astype(jnp.int32)
    rng = np.random.default_rng(1)
    meta = jnp.asarray(np.stack([rng.integers(0, 64, hot),
                                 rng.integers(0, 4, hot)]), jnp.int32)
    hsums = jnp.asarray(rng.integers(0, 64 * span, hot), jnp.float32)

    grid = []
    for bn in (8, 32, 128, 256):
        for bm in (128, 256, 512, 1024):
            if (_divisor_blocks(T, (bn,), 8)
                    and _divisor_blocks(m, (bm,), 128)):
                grid.append((bn, bm))
    predicted = [predict_hybrid_cost(N, hot, m, bn, bm, interpret)
                 for (bn, bm) in grid]
    survivors = prune(grid, predicted)
    if explain is not None:
        ranking = sorted(zip(grid, predicted), key=lambda t: t[1])
        explain["grid"] = len(grid)
        explain["predicted"] = [
            {"engine": "hybrid", "bn": bn, "bm": bm, "pred_us": p * 1e6}
            for (bn, bm), p in ranking]
        explain["survivors"] = len(survivors)

    results = []
    for bn, bm in survivors:
        try:
            dt = _measure(lambda: ops.classify_hybrid(
                q, 32, meta, hsums, cells, base, bn=bn, bm=bm,
                interpret=interpret)[0])
        except CANDIDATE_REFUSED:
            continue
        results.append({"engine": "hybrid", "bn": bn, "bm": bm,
                        "us": dt * 1e6})
        if verbose:
            print(f"  hybrid bn={bn} bm={bm}: {dt*1e3:.2f} ms")
    if not results:
        raise RuntimeError(f"no viable hybrid candidates N={N} m={m}")
    if explain is not None:
        explain["measured"] = sorted(results, key=lambda r: r["us"])
    return min(results, key=lambda r: r["us"])


def autotune_shapes(shapes, *, shard_counts=(), interpret: bool | None = None,
                    verbose: bool = False, observer=None,
                    explains: dict | None = None) -> dict:
    """Sweep (N, m) shapes (and shard counts); returns {table_key: cfg}.

    ``observer`` (a ``repro.obs.Observer``) gets one ``autotune.sweep``
    span per (op, shape) with the search counters as attributes; the
    running module-level tallies live in ``SEARCH_STATS``."""
    from repro.obs import resolve
    obs = resolve(observer)
    out = {}
    interp = resolve_interpret(interpret)

    def swept(op, N, m, fn, **kw):
        before = dict(SEARCH_STATS)
        exp = {}
        with obs.trace.span("autotune.sweep", op=op, N=N, m=m, **kw) as span:
            best = fn(explain=exp)
            span.set(
                candidates=SEARCH_STATS["candidates"] - before["candidates"],
                pruned=SEARCH_STATS["pruned"] - before["pruned"],
                measured=SEARCH_STATS["measured"] - before["measured"],
                winner=json.dumps(best, sort_keys=True))
        for k in SEARCH_STATS:
            obs.metrics.counter(f"autotune.{k}", op=op).inc(
                SEARCH_STATS[k] - before[k])
        if explains is not None:
            explains[key_for(op, N, kw.get("M", N), m, interp,
                             kw.get("shards", 1))] = exp
        if verbose:
            print(f"  -> {best}")
        return best

    for N, m in shapes:
        if verbose:
            print(f"[autotune] matrix N={N} m={m}")
        out[key_for("matrix", N, N, m, interp)] = swept(
            "matrix", N, m,
            lambda explain: autotune_matrix(
                N, m, interpret=interpret, verbose=verbose, explain=explain))
        if verbose:
            print(f"[autotune] one_vs_many N={N} m={m}")
        out[key_for("one_vs_many", N, N, m, interp)] = swept(
            "one_vs_many", N, m,
            lambda explain: autotune_one_vs_many(
                N, m, interpret=interpret, verbose=verbose, explain=explain))
        hot = max(8, N // 8)
        if verbose:
            print(f"[autotune] hybrid N={N} hot={hot} m={m}")
        out[key_for("hybrid", N, hot, m, interp)] = swept(
            "hybrid", N, m,
            lambda explain, hot=hot: autotune_hybrid(
                N, m, hot=hot, interpret=interpret, verbose=verbose,
                explain=explain),
            M=hot)
        for d in shard_counts:
            if d < 2 or N % d:
                continue
            if verbose:
                print(f"[autotune] matrix_sharded N={N} m={m} shards={d}")
            out[key_for("matrix_sharded", N, N, m, interp, d)] = swept(
                "matrix_sharded", N, m,
                lambda explain, d=d: autotune_matrix_sharded(
                    N, m, d, interpret=interpret, verbose=verbose,
                    explain=explain),
                shards=d)
    return out


def _print_explain(explains: dict) -> str:
    """Human-readable predicted-vs-measured report; returns the text."""
    lines = []
    for key, exp in sorted(explains.items()):
        pred = exp.get("predicted", [])
        meas = exp.get("measured", [])
        lines.append(f"== {key} ==")
        if "grid" in exp:
            lines.append(
                f"   grid {exp['grid']} candidates -> "
                f"{exp['survivors']} measured "
                f"({exp['grid'] - exp['survivors']} pruned by cost model)")
        lines.append("   predicted ranking          | measured")
        n = max(len(pred), len(meas))
        for i in range(n):
            left = right = ""
            if i < len(pred):
                p = dict(pred[i])
                us = p.pop("pred_us")
                left = f"{_cfg_str(p)} ~{us/1e3:.1f}ms"
            if i < len(meas):
                r = dict(meas[i])
                us = r.pop("us")
                right = f"{_cfg_str(r)} {us/1e3:.1f}ms"
            lines.append(f"   {left:<27}| {right}")
        if meas:
            win = dict(meas[0])
            win.pop("us", None)
            ranked = [
                {k: v for k, v in dict(p).items() if k != "pred_us"}
                for p in pred]
            try:
                lines.append(
                    f"   measured winner predicted at rank "
                    f"{ranked.index(win) + 1}/{len(ranked)}")
            except ValueError:
                pass
    text = "\n".join(lines)
    print(text)
    return text


def _cfg_str(cfg: dict) -> str:
    return " ".join(f"{k}={v}" for k, v in sorted(cfg.items()))


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--sizes", nargs="*", default=["256x512", "1024x1024"],
                   help="NxM cell-slab shapes to sweep (peers x cells)")
    p.add_argument("--shards", nargs="*", type=int, default=[],
                   help="also tune ring-vs-replicated at these shard counts")
    p.add_argument("--write", action="store_true",
                   help="merge results into the autotune table on disk")
    p.add_argument("--out", type=Path, default=None)
    p.add_argument("--explain", action="store_true",
                   help="print the cost model's predicted ranking next to "
                        "the measured winner for every (op, shape bucket)")
    p.add_argument("--explain-out", type=Path, default=None,
                   help="also write the --explain report to this file")
    p.add_argument("--trace-dir", type=Path, default=None,
                   help="record autotune.sweep spans + search counters "
                        "through a repro.obs Observer into this directory")
    args = p.parse_args(argv)
    shapes = [tuple(int(v) for v in s.split("x")) for s in args.sizes]

    observer = None
    if args.trace_dir is not None:
        from repro.obs import Observer
        observer = Observer.to_dir(args.trace_dir)
    explains: dict | None = {} if (args.explain or args.explain_out) else None
    results = autotune_shapes(shapes, shard_counts=tuple(args.shards),
                              verbose=True, observer=observer,
                              explains=explains)
    if observer is not None:
        observer.close()
    if explains is not None:
        text = _print_explain(explains)
        if args.explain_out is not None:
            args.explain_out.write_text(text + "\n")
    if args.write:
        table = dict(load_table())
        table.update(results)
        path = save_table(table, args.out)
        print(f"wrote {len(results)} entries -> {path}")
    else:
        print(json.dumps(results, indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
