"""CausalEngine: the single dispatch front-door over all compare engines.

Two verbs, every engine behind them:

    engine = CausalEngine(CausalPolicy(...))
    engine.classify(query, peers)   # one-vs-many -> ClassifyResult
    engine.pairs(clocks)            # all-pairs   -> ComparisonMatrix

This is the one layer that chooses a kernel: pack-on-the-fly vs the
int32 fallback, the tri / full / MXU-thermometer engine, the sharded
strategy (ring or replicated), the promoted-row overlay/rim for slab
rows whose value span outgrew a byte, alive-slot compaction and
dead-slot masking, and every kernel block shape (``blocks``: explicit
argument > policy > measured autotune table > backend default).
``kernels.ops`` runs what it chose, and each result is labelled
(``engine``, ``blocks``) from the choice its own call made.

Inputs: a ``PackedSlab`` (the registry's quantized u8 layout, promoted
rows included), an ``[N, m]`` int32 logical-cell slab, or a batched
``BloomClock``.  Outputs are the typed pytrees in ``causal.results``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.causal.policy import _ENGINES, CausalPolicy
from repro.causal.results import ClassifyResult, Comparison, ComparisonMatrix
from repro.core import clock as bc
from repro.kernels import autotune, ops, pack
from repro.kernels.pack import U8_MAX
from repro.kernels.template import resolve_interpret
from repro.obs.observer import resolve

# the int32 one-vs-many's blocks when neither the call nor the policy
# names any: the int32 rim of a packed slab always runs at these
_I32_BLOCKS = {"bn": 8, "bm": 512}

__all__ = ["CausalEngine", "PackedSlab", "compare"]


def compare(a: bc.BloomClock, b: bc.BloomClock) -> Comparison:
    """Pairwise (broadcast/batched) typed comparison of two clocks.

    The reference partial-order + Eq. 3 math from ``repro.core.clock``,
    returned as a ``Comparison`` pytree; jit/vmap composable.
    """
    o = bc.ordering(a, b)
    return Comparison(a_le_b=o.a_le_b, b_le_a=o.b_le_a,
                      fp_ab=o.fp_a_before_b, fp_ba=o.fp_b_before_a,
                      sum_a=bc.clock_sum(a), sum_b=bc.clock_sum(b))


@dataclasses.dataclass
class PackedSlab:
    """Packed peer-clock slab view handed to the front-door.

    The §4 quantized layout (``kernels.pack``): u8 window residuals
    plus a per-slot int32 base.  ``wide`` carries promoted rows — slots
    whose residual span outgrew a byte — as host int32 logical rows;
    the engine overlays them through the exact int32 kernel so they
    never sink the bulk to the fallback.  ``base_host`` (optional) lets
    the engine probe base uniformity without a device sync.
    """

    cells_u8: jax.Array                       # [N, m] uint8 residuals
    base: jax.Array                           # [N] int32 offsets
    base_host: Optional[np.ndarray] = None    # host copy of ``base``
    wide: dict = dataclasses.field(default_factory=dict)

    @property
    def capacity(self) -> int:
        return self.cells_u8.shape[0]

    @property
    def m(self) -> int:
        return self.cells_u8.shape[1]

    @property
    def packed(self) -> bool:
        return not self.wide


def _as_cells(clocks) -> jax.Array:
    """int32 logical cells from a BloomClock (any batch shape) or array."""
    if isinstance(clocks, bc.BloomClock):
        return clocks.logical_cells().astype(jnp.int32)
    return jnp.asarray(clocks, jnp.int32)


class CausalEngine:
    """The two-verb causality front-door (see module docstring)."""

    def __init__(self, policy: CausalPolicy | None = None):
        self.policy = policy or CausalPolicy()
        # instrumentation rides the policy; null sinks when absent
        self.obs = resolve(getattr(self.policy, "observer", None))

    def _record_dispatch(self, verb: str, res, n: int, span) -> None:
        """Span attrs + the dispatch counter for one front-door call."""
        span.set(engine=res.engine, n=n,
                 blocks=dict(res.blocks) if res.blocks else None,
                 shards=self.policy.shards)
        self.obs.metrics.counter("engine_dispatch", verb=verb,
                                 engine=res.engine).inc()

    def _interpret(self, interpret: bool | None) -> bool:
        return resolve_interpret(
            interpret if interpret is not None else self.policy.interpret)

    # ------------------------------------------------------------------
    # block shapes
    # ------------------------------------------------------------------
    def _lookup(self, op: str, N: int, M: int, m: int, interpret: bool,
                shards: int = 1) -> dict:
        cfg = autotune.lookup(op, N, M, m, interpret, shards)
        if self.obs:
            self.obs.metrics.counter(
                "autotune_cache",
                outcome="miss" if cfg is None else "hit").inc()
        return cfg or {}

    def blocks(self, op: str, N: int, M: int, m: int, *,
               interpret: bool | None = None, engine: str | None = None,
               shards: int = 1, **explicit) -> dict:
        """Kernel block shapes for one call: an explicit argument, else
        the policy's, else the measured autotune table (when
        ``policy.autotune``), else the backend default.

        ``op`` is ``"one_vs_many"`` (keyed on N rows), ``"hybrid"``
        (keyed on N total rows and M hot rows: the split changes the
        winning tile), ``"i32"`` (the int32 one-vs-many; no table) or
        ``"matrix"`` for ``engine`` over [N, M] pairs.  A sharded matrix
        (``shards > 1``) reads only the ``matrix_sharded`` entry of the
        GLOBAL shape and shard count, so a d-shard tune and a 1-shard
        tune whose shapes collide never poison each other."""
        pol = self.policy
        interpret = self._interpret(interpret)
        keys = ("bi", "bj", "bm") if op == "matrix" else ("bn", "bm")
        got = {k: explicit.get(k) if explicit.get(k) is not None
               else getattr(pol, k) for k in keys}
        if all(got.values()):
            return got
        cfg = {}
        if pol.autotune and op != "i32":
            if op == "matrix" and shards > 1:
                cfg = self._lookup("matrix_sharded", N, M, m, interpret,
                                   shards)
            else:
                cfg = self._lookup(op, N, M, m, interpret)
                if op == "matrix" and cfg.get("engine") != engine:
                    cfg = {}
        if op == "matrix":
            # square 128 blocks: lane-aligned flag outputs on the chip,
            # and the 8-row int32 pairwise difference well inside VMEM;
            # the chip's thermometer streams 128-cell m-tiles
            dflt = {"bi": 128, "bj": 128,
                    "bm": 128 if engine == "mxu" and not interpret else 512}
        elif op == "i32":
            dflt = _I32_BLOCKS
        else:
            dflt = {"bn": 128 if interpret else 512, "bm": 512}
        return {k: got[k] or cfg.get(k, dflt[k]) for k in keys}

    # ------------------------------------------------------------------
    # verb 1: one-vs-many classify
    # ------------------------------------------------------------------
    def classify(self, query, peers, *, bn: int | None = None,
                 bm: int | None = None,
                 interpret: bool | None = None) -> ClassifyResult:
        """Classify one query clock against N peers in one device call.

        ``query``: a ``BloomClock`` or ``[m]`` int32 logical cells.
        ``peers``: a ``PackedSlab`` (u8 kernel, shard_map'd when the
        policy carries a mesh, promoted rows overlaid exactly; with a
        hot set, the fused hybrid kernel) or an ``[N, m]`` int32 slab /
        batched ``BloomClock`` (int32 kernel).
        """
        obs = self.obs
        if not obs:
            return self._classify(query, peers, bn=bn, bm=bm,
                                  interpret=interpret)
        n = peers.capacity if isinstance(peers, PackedSlab) else -1
        with obs.trace.span("causal.classify",
                            pack="slab" if isinstance(peers, PackedSlab)
                            else "i32") as sp:
            res = self._classify(query, peers, bn=bn, bm=bm,
                                 interpret=interpret)
            if n < 0:
                n = int(np.shape(res.sum_p)[-1])
            self._record_dispatch("classify", res, n, sp)
        return res

    def _classify(self, query, peers, *, bn, bm, interpret) -> ClassifyResult:
        pol = self.policy
        q = _as_cells(query)
        interpret = self._interpret(interpret)
        if not isinstance(peers, PackedSlab):
            cells = _as_cells(peers)
            N, m = cells.shape
            b = self.blocks("i32", N, N, m, interpret=interpret, bn=bn,
                            bm=bm)
            out = ops.classify_i32(q, cells, **b, interpret=interpret)
            return ClassifyResult.from_dict(out, engine="i32")
        N, m = peers.capacity, peers.m
        hot_meta = getattr(peers, "hot_meta", None)
        H = 0 if hot_meta is None else int(np.shape(hot_meta)[0])
        if H:
            # one fused sweep over the exact hot rows and the packed
            # tail; result rows are hot-first, [0, H) hot.  The hot set
            # is a handful of metadata rows, so it stays unsharded even
            # under a mesh policy
            b = self.blocks("hybrid", H + N, H, m, interpret=interpret,
                            bn=bn, bm=bm)
            # the kernel reads the [H, 2] metadata as [2, H] dense rows
            # (a free view of the row snapshot ``HybridEngine`` keeps)
            out, (bn_run, bm_run) = ops.classify_hybrid(
                q, int(peers.local_version), hot_meta.T, peers.hot_sums,
                peers.cells_u8, peers.base, **b, interpret=interpret)
            engine = "fused_hot_tail"
            blocks = _label(bn=bn_run, bm=bm_run, hot=H, tail=N)
        else:
            b = self.blocks("one_vs_many", N, N, m, interpret=interpret,
                            bn=bn, bm=bm)
            if pol.mesh is not None:
                out = ops.classify_packed_sharded(
                    q, peers.cells_u8, peers.base, mesh=pol.mesh,
                    axis=pol.axis, **b, interpret=interpret)
                engine = "packed_sharded"
                blocks = _label(**b, shards=pol.shards)
            else:
                out = ops.classify_packed(q, peers.cells_u8, peers.base,
                                          **b, interpret=interpret)
                engine = "packed"
                blocks = _label(**b)
        if peers.wide:
            # wide keys index TAIL slots; result rows shift by the hot
            # block, and the overlay patches the shifted positions
            widx = sorted(peers.wide)
            out = _overlay_wide(
                out, q, [H + s for s in widx],
                jnp.asarray(np.stack([peers.wide[s] for s in widx])),
                interpret)
            engine += "+wide_overlay"
        return ClassifyResult.from_dict(out, engine=engine, blocks=blocks)

    # ------------------------------------------------------------------
    # verb 2: all-pairs compare
    # ------------------------------------------------------------------
    def pairs(self, clocks, cols=None, *, alive: np.ndarray | None = None,
              alive_dev: jax.Array | None = None,
              engine: str | None = None, bi: int | None = None,
              bj: int | None = None, bm: int | None = None,
              uniform_base: bool | None = None,
              interpret: bool | None = None) -> ComparisonMatrix:
        """All-pairs partial order + Eq. 3 fp over a batch of clocks.

        ``clocks``: a ``PackedSlab`` (symmetric; honors ``alive`` slot
        masking, promoted-row rims and the policy mesh) or an
        ``[N, m]`` int32 slab / batched ``BloomClock`` — optionally vs
        a second ``cols`` slab — where the engine packs on the fly when
        the value span fits a byte and falls back to the int32 kernel
        otherwise.

        ``alive``: host bool mask over slab slots; dead slots cost no
        compute (alive-compacted unsharded / masked sharded) and report
        all-False flags, zero fp and zero sums.  ``alive_dev`` is an
        optional pre-placed device copy (a sharded registry passes its
        mesh-placed mask so masking never re-uploads).
        """
        obs = self.obs
        if not obs:
            return self._pairs(clocks, cols, alive=alive,
                               alive_dev=alive_dev, engine=engine, bi=bi,
                               bj=bj, bm=bm, uniform_base=uniform_base,
                               interpret=interpret)
        with obs.trace.span("causal.pairs",
                            pack="slab" if isinstance(clocks, PackedSlab)
                            else "i32") as sp:
            res = self._pairs(clocks, cols, alive=alive,
                              alive_dev=alive_dev, engine=engine, bi=bi,
                              bj=bj, bm=bm, uniform_base=uniform_base,
                              interpret=interpret)
            self._record_dispatch("pairs", res, int(np.shape(res.le)[0]),
                                  sp)
        return res

    def _pairs(self, clocks, cols=None, *, alive=None, alive_dev=None,
               engine=None, bi=None, bj=None, bm=None, uniform_base=None,
               interpret=None) -> ComparisonMatrix:
        pol = self.policy
        engine = engine if engine is not None else pol.engine
        if engine not in _ENGINES:
            raise ValueError(f"unknown engine {engine!r}")
        interpret = self._interpret(interpret)
        blk = {"bi": bi, "bj": bj, "bm": bm}
        if isinstance(clocks, PackedSlab):
            if getattr(clocks, "hot_meta", None) is not None:
                raise ValueError(
                    "hot-carrying slabs are classify-only here; use "
                    "repro.hybrid.HybridEngine.pairs for the fused "
                    "all-pairs sweep")
            if cols is not None:
                raise ValueError(
                    "PackedSlab pairs are symmetric; cols is not supported")
            return self._pairs_slab(clocks, alive, alive_dev, engine,
                                    blk, uniform_base, interpret)
        if alive is not None or alive_dev is not None:
            raise ValueError("alive masking needs a PackedSlab input")
        rows = _as_cells(clocks)
        if engine is None and not pol.pack:
            engine = "i32"
        cols_c = rows if cols is None else _as_cells(cols)
        out, eng, blocks = self._compare_cells(rows, cols_c, engine, blk,
                                               interpret)
        return ComparisonMatrix.from_dict(out, engine=eng, blocks=blocks)

    def _compare_cells(self, rows, cols, engine, blk, interpret):
        """int32 logical slabs: packed on the fly (shared window base,
        uniform-base fast path) when the global value span fits a byte,
        the int32 kernel otherwise.  Returns (result, engine, blocks)."""
        symmetric = rows is cols
        N, m = rows.shape
        M, mc = cols.shape
        assert m == mc, (rows.shape, cols.shape)
        if engine is None and isinstance(rows, jax.core.Tracer):
            engine = "i32"      # under an outer jit the span probe can't sync
        if engine is None and self.policy.autotune and self._lookup(
                "matrix", N, M, m, interpret).get("engine") == "i32":
            # a measured "int32 wins here" verdict skips the probe
            engine = "i32"
        if engine != "i32":
            lo, hi = (int(v) for v in jax.device_get(
                _span_probe(rows, None if symmetric else cols)))
            if hi - lo <= U8_MAX:
                packed_rows = _shift_pack(rows, lo)
                base = jnp.full((N,), lo, jnp.int32)
                if symmetric:
                    return self._compare_packed(
                        packed_rows, base, None, None, engine, blk, True,
                        interpret)
                return self._compare_packed(
                    packed_rows, base, _shift_pack(cols, lo),
                    jnp.full((M,), lo, jnp.int32), engine, blk, True,
                    interpret)
            if engine is not None:
                raise ValueError(
                    f"engine={engine} needs value span <= {U8_MAX}, "
                    f"got {hi - lo}")
        b = self.blocks("matrix", N, M, m, interpret=interpret,
                        engine="i32", **blk)
        return (ops.compare_i32(rows, cols, **b, interpret=interpret),
                "i32", _label(**b))

    def _packed_engine(self, cells, base, cols, col_base, engine,
                       symmetric: bool, interpret: bool):
        """(engine, mxu bounds) for a packed compare.  No preference (or
        the int32 hint, which a packed slab cannot run — its flags are
        exact under every packed engine) reads the table, default tri;
        the MXU thermometer needs a concrete value span within
        ``MXU_SPAN_MAX``; tri needs a symmetric compare."""
        N, m = cells.shape
        bounds = None
        if engine in (None, "i32"):
            engine = (self._lookup("matrix", N, cols.shape[0], m,
                                   interpret).get("engine", "tri")
                      if self.policy.autotune else "tri")
            if engine == "mxu":
                bounds = _mxu_bounds(cells, base, cols, col_base)
            if engine == "i32" or (engine == "mxu" and bounds is None):
                engine = "tri"
        elif engine == "mxu":
            bounds = ops.logical_bounds(cells, base, cols, col_base)
        if engine == "tri" and not symmetric:
            engine = "full"
        return engine, bounds

    def _compare_packed(self, cells, base, cols, col_base, engine, blk,
                        uniform_base, interpret):
        """All-pairs over packed slab(s) on one device.  Returns
        (result, engine, blocks)."""
        symmetric = cols is None
        c, cb = (cells, base) if symmetric else (cols, col_base)
        engine, bounds = self._packed_engine(cells, base, c, cb, engine,
                                             symmetric, interpret)
        if uniform_base is None:
            uniform_base = _uniform(base, cb)
        b = self.blocks("matrix", cells.shape[0], c.shape[0],
                        cells.shape[1], interpret=interpret, engine=engine,
                        **blk)
        out = ops.compare_packed(cells, base, cols, col_base, engine=engine,
                                 **b, uniform_base=uniform_base,
                                 interpret=interpret, bounds=bounds)
        return out, engine, _label(**b)

    def _pairs_sharded(self, slab: PackedSlab, blk, uniform_base,
                       interpret, mesh_outputs: bool):
        """Symmetric all-pairs over the mesh-sharded slab, by the
        strategy the table's ``matrix_sharded`` entry names (ring when
        it is silent): the halved ppermute ring, or the slab gathered
        onto one device under the single-device engine.  An engine
        preference has no per-tile meaning on the ring (off-diagonal
        tiles are rectangles), so the ring runs ``full`` and the
        replicated sweep its own auto engine.  Returns (result, engine,
        blocks)."""
        pol = self.policy
        N, m, d = slab.capacity, slab.m, pol.shards
        cells, base = slab.cells_u8, slab.base
        if uniform_base is None:
            uniform_base = _uniform(base)
        strategy = (self._lookup("matrix_sharded", N, N, m, interpret,
                                 d).get("strategy", "ring")
                    if pol.autotune else "ring")
        if strategy == "replicated":
            engine, bounds = self._packed_engine(
                cells, base, cells, base, None, True, interpret)
            b = self.blocks("matrix", N, N, m, interpret=interpret,
                            engine=engine, **blk)
            out = ops.compare_replicated(
                cells, base, mesh=pol.mesh, axis=pol.axis, engine=engine,
                **b, uniform_base=uniform_base, interpret=interpret,
                mesh_outputs=mesh_outputs, bounds=bounds)
            return (out, f"replicated_{engine}",
                    _label(**b, shards=d, strategy="replicated"))
        if strategy != "ring":
            raise ValueError(f"unknown sharded strategy: {strategy}")
        b = self.blocks("matrix", N, N, m, interpret=interpret,
                        engine="full", shards=d, **blk)
        out = ops.compare_ring(cells, base, mesh=pol.mesh, axis=pol.axis,
                               **b, uniform_base=uniform_base,
                               interpret=interpret)
        return out, "ring_full", _label(**b, shards=d, strategy="ring")

    # ---- packed-slab assembly (compaction, promoted rims, masking) ----
    def _pairs_slab(self, slab: PackedSlab, alive, alive_dev, engine,
                    blk, uniform_base, interpret) -> ComparisonMatrix:
        cap = slab.capacity
        alive = (np.ones(cap, bool) if alive is None
                 else np.asarray(alive, bool))
        aidx = np.flatnonzero(alive)
        if aidx.size == 0:
            false = jnp.zeros((cap, cap), bool)
            return ComparisonMatrix(
                le=false, ge=false, conc=false,
                fp=jnp.zeros((cap, cap), jnp.float32),
                row_sums=jnp.zeros((cap,), jnp.float32),
                col_sums=jnp.zeros((cap,), jnp.float32), engine="empty")
        if uniform_base is None:
            uniform_base = self._uniform_base(slab, alive)
        full = aidx.size == cap and slab.packed
        if self.policy.mesh is not None:
            # mesh placement only matters when the bulk is combined with
            # sharded masks/overlays below; the fully-alive packed fast
            # path returns it as-is, so the replicated strategy may skip
            # its output reshard
            bulk, eng, blocks = self._pairs_sharded(
                slab, blk, uniform_base, interpret, mesh_outputs=not full)
            if full:
                return ComparisonMatrix.from_dict(bulk, engine=eng,
                                                  blocks=blocks)
            if not slab.packed:
                # promoted rows: patch the O(P * A) int32 rim into the
                # bulk ON DEVICE — the [cap, cap] matrices stay sharded
                bulk = self._device_wide_overlay(slab, bulk, aidx, blk,
                                                 interpret)
                eng += "+wide_rim"
            # dead slots report nothing; masking is device-side too, so
            # a huge sharded fleet never materializes flags on host
            al = alive_dev if alive_dev is not None else jnp.asarray(alive)
            return ComparisonMatrix.from_dict(
                _mask_dead_pairs(bulk, al), engine=eng, blocks=blocks)
        if full:
            out, eng, blocks = self._compare_packed(
                slab.cells_u8, slab.base, None, None, engine, blk,
                uniform_base, interpret)
            return ComparisonMatrix.from_dict(out, engine=eng, blocks=blocks)
        if slab.packed:
            # gather the alive rows into a dense sub-slab: dead slots
            # cost no compute, results scatter back to full capacity
            jidx = jnp.asarray(aidx)
            sub, eng, blocks = self._compare_packed(
                jnp.take(slab.cells_u8, jidx, axis=0),
                jnp.take(slab.base, jidx), None, None, engine, blk,
                uniform_base, interpret)
            return ComparisonMatrix.from_dict(
                _expand_alive(sub, jidx, cap), engine=eng, blocks=blocks)
        return self._host_pairs(slab, alive, aidx, engine, blk, interpret)

    @staticmethod
    def _uniform_base(slab: PackedSlab, alive: np.ndarray) -> bool | None:
        """Host-side base-uniformity probe over the alive rows; None
        (a device probe later) when no host base copy is carried."""
        if slab.base_host is None:
            return None
        b = np.asarray(slab.base_host)[alive]
        return bool(b.size == 0 or (b == b[0]).all())

    @staticmethod
    def _alive_widx(slab: PackedSlab, aidx: np.ndarray) -> np.ndarray:
        """Promoted slots restricted to the given alive index set."""
        keep = set(int(s) for s in aidx)
        return np.asarray(
            sorted(s for s in slab.wide if s in keep), np.int64)

    def _wide_rim(self, slab: PackedSlab, aidx: np.ndarray,
                  widx: np.ndarray, blk, interpret) -> dict:
        """Exact int32 compare of the promoted rows vs every alive row
        ([P, A]).  Unpacks ONLY the gathered alive rows — never the
        full-capacity slab — and patches the promoted rows' true values
        over their clipped residuals.

        Known scale limit (ROADMAP): a Pallas kernel cannot be
        partitioned automatically, so on a mesh-sharded slab the
        gathered alive rows move to ONE mesh device and the rim runs
        there, concentrating ~4x the alive u8 bytes on it; a shard-wise
        rim (wide rows replicated vs each row shard under shard_map)
        would remove that.  Promoted rows contradict the §4
        moving-window premise, so fleets sharded for scale should treat
        them as an eviction signal, not steady state."""
        wide_rows = jnp.asarray(
            np.stack([slab.wide[int(s)] for s in widx]))
        jaidx = jnp.asarray(aidx)
        rows = (jnp.take(slab.cells_u8, jaidx, axis=0),
                jnp.take(slab.base, jaidx))
        if self.policy.mesh is not None:
            rows = jax.device_put(rows, self.policy.mesh.devices.flat[0])
        alive_i32 = pack.unpack_rows(*rows)
        wpos = {int(s): i for i, s in enumerate(aidx)}
        alive_i32 = alive_i32.at[
            jnp.asarray([wpos[int(s)] for s in widx])].set(wide_rows)
        # a promoted row's span exceeds a byte BY DEFINITION: name the
        # int32 engine outright and skip the futile span probe
        out, _, _ = self._compare_cells(wide_rows, alive_i32, "i32", blk,
                                        interpret)
        return out

    def _device_wide_overlay(self, slab: PackedSlab, bulk: dict,
                             aidx: np.ndarray, blk, interpret) -> dict:
        """Patch the promoted rows'/cols' flags into the sharded bulk and
        re-finalize fp from corrected sums, entirely ON DEVICE — the
        [cap, cap] matrices stay sharded, so even a promoted row on a
        fleet too large for one device costs only the O(P * cap) rim."""
        cap, m = slab.capacity, slab.m
        widx = self._alive_widx(slab, aidx)
        if widx.size == 0:
            return bulk
        # the rim ran on one device: replicate it over the mesh to patch
        # the sharded bulk
        rim = jax.device_put(self._wide_rim(slab, aidx, widx, blk,
                                            interpret),
                             NamedSharding(self.policy.mesh, P()))
        jw = jnp.asarray(widx)
        jaidx = jnp.asarray(aidx)
        n_wide = int(widx.size)

        def patch(mat, row_pa, col_pa):
            rows_full = jnp.zeros((n_wide, cap), bool).at[:, jaidx].set(
                row_pa)
            cols_full = jnp.zeros((n_wide, cap), bool).at[:, jaidx].set(
                col_pa)
            mat = jnp.asarray(mat, bool).at[jw, :].set(rows_full)
            return mat.at[:, jw].set(cols_full.T)

        le = patch(bulk["a_le_b"], rim["a_le_b"], rim["b_le_a"])
        ge = patch(bulk["b_le_a"], rim["b_le_a"], rim["a_le_b"])
        sums = jnp.asarray(bulk["row_sums"]).at[jw].set(rim["row_sums"])
        return {
            "a_le_b": le, "b_le_a": ge,
            "concurrent": jnp.logical_not(jnp.logical_or(le, ge)),
            # same jitted Eq. 3 expression as every engine finalize, over
            # the corrected sums -> bit-identical to the unsharded path
            "fp": ops.eq3_outer(sums, sums, m),
            "row_sums": sums, "col_sums": sums,
        }

    def _host_pairs(self, slab: PackedSlab, alive: np.ndarray,
                    aidx: np.ndarray, engine, blk,
                    interpret) -> ComparisonMatrix:
        """Unsharded sparse promoted-row assembly: packed engines over
        the still-packed alive rows plus the exact int32 rim for the
        promoted handful, stitched on host (the slab already lives on
        one device here — the sharded path patches on device instead,
        see ``_device_wide_overlay``).  fp is re-finalized from the
        corrected sums through the SAME jitted Eq. 3 expression the
        engines use (``ops.eq3_outer``), so values stay bit-identical
        to the single-device int32 fallback this replaces."""
        cap, m = slab.capacity, slab.m
        widx = self._alive_widx(slab, aidx)
        le = np.zeros((cap, cap), bool)
        ge = np.zeros((cap, cap), bool)
        sums = np.zeros(cap, np.float32)
        pidx = np.asarray([s for s in aidx if s not in slab.wide],
                          np.int64)
        eng = "none"
        if pidx.size:
            if slab.base_host is not None:
                b = slab.base_host[pidx]
                uniform = bool((b == b[0]).all())
            else:
                uniform = None     # no host copy: probe on the device
            sub, eng, _ = self._compare_packed(
                jnp.take(slab.cells_u8, jnp.asarray(pidx), axis=0),
                jnp.take(slab.base, jnp.asarray(pidx)), None, None,
                engine, blk, uniform, interpret)
            sub = jax.device_get(sub)
            le[np.ix_(pidx, pidx)] = sub["a_le_b"]
            ge[np.ix_(pidx, pidx)] = sub["b_le_a"]
            sums[pidx] = sub["row_sums"]
        if widx.size:
            rim = jax.device_get(self._wide_rim(slab, aidx, widx, blk,
                                                interpret))
            eng += "+wide_rim"
            le[np.ix_(widx, aidx)] = rim["a_le_b"]
            ge[np.ix_(widx, aidx)] = rim["b_le_a"]
            le[np.ix_(aidx, widx)] = rim["b_le_a"].T
            ge[np.ix_(aidx, widx)] = rim["a_le_b"].T
            sums[widx] = rim["row_sums"]
        le[~alive] = False
        le[:, ~alive] = False
        ge[~alive] = False
        ge[:, ~alive] = False
        sums[~alive] = 0.0
        pair = np.ix_(aidx, aidx)
        conc = np.zeros((cap, cap), bool)
        conc[pair] = ~(le[pair] | ge[pair])
        fp = np.zeros((cap, cap), np.float32)
        fp[pair] = np.asarray(ops.eq3_outer(
            jnp.asarray(sums[aidx]), jnp.asarray(sums[aidx]), m))
        s = jnp.asarray(sums)
        return ComparisonMatrix(
            le=jnp.asarray(le), ge=jnp.asarray(ge), conc=jnp.asarray(conc),
            fp=jnp.asarray(fp), row_sums=s, col_sums=s, engine=eng)


def _label(**blocks) -> tuple:
    """A result's ``blocks`` metadata: its block shapes and placement,
    as sorted (name, value) pairs."""
    return tuple(sorted(blocks.items()))


def _overlay_wide(out: dict, q: jax.Array, wide_idx, wide_rows: jax.Array,
                  interpret: bool) -> dict:
    """Sparse promoted-row overlay for one-vs-many classify results.

    ``out`` is a packed-slab result dict whose promoted slots hold
    garbage (their u8 residuals were clipped at promotion); re-classify
    JUST the ``[P, m]`` promoted rows through the exact int32 kernel and
    patch them in.  The O(N) bulk stays packed — a single overflowed row
    never drops the whole slab compare to the int32 fallback.
    """
    wout = ops.classify_i32(q, wide_rows, **_I32_BLOCKS, interpret=interpret)
    idx = jnp.asarray(wide_idx, jnp.int32)
    patched = dict(out)
    for key in ("q_le_p", "p_le_q", "sum_p",
                "fp_q_before_p", "fp_p_before_q"):
        patched[key] = jnp.asarray(out[key]).at[idx].set(wout[key])
    return patched


def _uniform(base, col_base=None) -> bool:
    """Device probe: every base (of both slabs) equal to the first."""
    b = jnp.asarray(base).reshape(-1)
    if col_base is None:
        return bool((b == b[0]).all())
    cb = jnp.asarray(col_base).reshape(-1)
    return bool((b == b[0]).all()) and bool((cb == b[0]).all())


def _mxu_bounds(cells, base, cols, col_base):
    """(lo, span) when the MXU thermometer can run these slabs, else
    None: under an outer jit the host-synced bounds probe cannot run,
    and spans wider than ``MXU_SPAN_MAX`` go elementwise."""
    if isinstance(cells, jax.core.Tracer):
        return None
    lo, span = ops.logical_bounds(cells, base, cols, col_base)
    return (lo, span) if span <= ops.MXU_SPAN_MAX else None


@functools.partial(jax.jit, static_argnames=("lo",))
def _shift_pack(x, lo: int):
    return (jnp.asarray(x, jnp.int32) - lo).astype(jnp.uint8)


@jax.jit
def _span_probe(rows, cols=None):
    """[lo, hi] over one or two slabs, fetched in ONE host transfer."""
    lo, hi = jnp.min(rows), jnp.max(rows)
    if cols is not None:
        lo = jnp.minimum(lo, jnp.min(cols))
        hi = jnp.maximum(hi, jnp.max(cols))
    return jnp.stack([lo, hi])


@jax.jit
def _mask_dead_pairs(bulk: dict, alive: jax.Array) -> dict:
    """Device-side dead-slot masking of a full-capacity all-pairs bulk:
    the sharded ring's counterpart of ``_expand_alive`` (same contract —
    dead rows/cols report all-False flags and zero fp / sums)."""
    pair = alive[:, None] & alive[None, :]
    le = jnp.asarray(bulk["a_le_b"], bool) & pair
    ge = jnp.asarray(bulk["b_le_a"], bool) & pair
    sums = jnp.where(alive, bulk["row_sums"], 0.0)
    return {
        "a_le_b": le,
        "b_le_a": ge,
        "concurrent": jnp.logical_not(jnp.logical_or(le, ge)) & pair,
        "fp": jnp.where(pair, bulk["fp"], 0.0),
        "row_sums": sums,
        "col_sums": sums,
    }


def _expand_alive(sub: dict, jidx: jax.Array, cap: int) -> dict:
    """Scatter an alive-compacted result back to [capacity, capacity]."""
    rows = jidx[:, None]
    cols = jidx[None, :]

    def mat(x, fill, dtype):
        return jnp.full((cap, cap), fill, dtype).at[rows, cols].set(x)

    def vec(x):
        return jnp.zeros((cap,), x.dtype).at[jidx].set(x)

    return {
        "a_le_b": mat(sub["a_le_b"], False, bool),
        "b_le_a": mat(sub["b_le_a"], False, bool),
        "concurrent": mat(sub["concurrent"], False, bool),
        "fp": mat(sub["fp"], 0.0, jnp.float32),
        "row_sums": vec(sub["row_sums"]),
        "col_sums": vec(sub["col_sums"]),
    }
