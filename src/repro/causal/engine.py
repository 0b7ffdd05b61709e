"""CausalEngine: the single dispatch front-door over all compare engines.

Two verbs, every engine behind them:

    engine = CausalEngine(CausalPolicy(...))
    engine.classify(query, peers)   # one-vs-many -> ClassifyResult
    engine.pairs(clocks)            # all-pairs   -> ComparisonMatrix

Internally the front-door handles everything callers used to hand-roll
at eight different entry points: pack-on-the-fly vs the int32 fallback,
MXU-thermometer viability, the promoted-row overlay/rim for slab rows
whose value span outgrew a byte, alive-slot compaction and dead-slot
masking, and single-device vs shard_map'd sharded execution — all
consulting the measured autotune table through one resolution path and
reporting the choice it made in the result's ``engine`` metadata.

Inputs: a ``PackedSlab`` (the registry's quantized u8 layout, promoted
rows included), an ``[N, m]`` int32 logical-cell slab, or a batched
``BloomClock``.  Outputs are the typed pytrees in ``causal.results``;
their values are bit-identical to the pre-front-door entry points (the
``ops.*`` shims), which delegate to the same implementations.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.causal.policy import CausalPolicy
from repro.causal.results import ClassifyResult, Comparison, ComparisonMatrix
from repro.core import clock as bc
from repro.kernels import autotune, ops, pack
from repro.obs.observer import resolve

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


def _dispatch_label(fallback: str) -> tuple[str, tuple | None]:
    """(engine, blocks) metadata from the most recent ops dispatch."""
    d = ops.LAST_DISPATCH
    if not d:
        return fallback, None
    blocks = tuple((k, v) for k, v in sorted(d.items())
                   if k not in ("op", "engine"))
    return d.get("engine", fallback), blocks


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

    def _record_dispatch(self, verb: str, res, n: int, span,
                         tune0: tuple[int, int]) -> None:
        """Span attrs + dispatch counters for one front-door call."""
        obs = self.obs
        span.set(engine=res.engine, n=n,
                 blocks=dict(res.blocks) if res.blocks else None,
                 shards=self.policy.shards)
        obs.metrics.counter("engine_dispatch", verb=verb,
                            engine=res.engine).inc()
        hits = autotune.CACHE_STATS["hit"] - tune0[0]
        misses = autotune.CACHE_STATS["miss"] - tune0[1]
        if hits:
            obs.metrics.counter("autotune_cache", outcome="hit").inc(hits)
        if misses:
            obs.metrics.counter("autotune_cache", outcome="miss").inc(misses)

    # ------------------------------------------------------------------
    # verb 1: one-vs-many classify
    # ------------------------------------------------------------------
    def classify(self, query, peers, *, bn: int | None = None,
                 bm: int | None = None,
                 interpret: bool | None = None) -> ClassifyResult:
        """Classify one query clock against N peers in one device call.

        ``query``: a ``BloomClock`` or ``[m]`` int32 logical cells.
        ``peers``: a ``PackedSlab`` (u8 kernel, shard_map'd when the
        policy carries a mesh, promoted rows overlaid exactly) or an
        ``[N, m]`` int32 slab / batched ``BloomClock`` (int32 kernel).
        """
        obs = self.obs
        if not obs:
            return self._classify(query, peers, bn=bn, bm=bm,
                                  interpret=interpret)
        tune0 = (autotune.CACHE_STATS["hit"], autotune.CACHE_STATS["miss"])
        n = peers.capacity if isinstance(peers, PackedSlab) else -1
        with obs.trace.span("causal.classify",
                            pack="slab" if isinstance(peers, PackedSlab)
                            else "i32") as sp:
            res = self._classify(query, peers, bn=bn, bm=bm,
                                 interpret=interpret)
            if n < 0:
                n = int(np.shape(res.sum_p)[-1])
            self._record_dispatch("classify", res, n, sp, tune0)
        return res

    def _classify(self, query, peers, *, bn, bm, interpret) -> ClassifyResult:
        pol = self.policy
        q = _as_cells(query)
        bn = bn if bn is not None else pol.bn
        bm = bm if bm is not None else pol.bm
        interpret = interpret if interpret is not None else pol.interpret
        ops.LAST_DISPATCH.clear()
        if isinstance(peers, PackedSlab):
            hot_meta = getattr(peers, "hot_meta", None)
            if hot_meta is not None and np.shape(hot_meta)[0] > 0:
                return self._classify_hybrid(q, peers, bn, bm, interpret)
            if pol.mesh is not None:
                out = ops._classify_vs_many_packed_sharded(
                    q, peers.cells_u8, peers.base, mesh=pol.mesh,
                    axis=pol.axis, bn=bn, bm=bm, interpret=interpret,
                    use_autotune=pol.autotune)
            else:
                out = ops._classify_vs_many_packed(
                    q, peers.cells_u8, peers.base, bn=bn, bm=bm,
                    interpret=interpret, use_autotune=pol.autotune)
            engine, blocks = _dispatch_label("packed")
            if peers.wide:
                widx = sorted(peers.wide)
                out = ops._overlay_wide_classify(
                    out, q, widx,
                    jnp.asarray(np.stack([peers.wide[s] for s in widx])),
                    interpret=interpret)
                engine += "+wide_overlay"
            return ClassifyResult.from_dict(out, engine=engine,
                                            blocks=blocks)
        cells = _as_cells(peers)
        kw = {}
        if bn is not None:
            kw["bn"] = bn
        if bm is not None:
            kw["bm"] = bm
        out = ops._classify_vs_many(q, cells, interpret=interpret, **kw)
        return ClassifyResult.from_dict(out, engine="i32")

    def _classify_hybrid(self, q, peers, bn, bm, interpret) -> ClassifyResult:
        """Hot-carrying slab (``repro.hybrid.HybridSlab``-shaped, duck
        typed on ``hot_meta``): ONE fused kernel sweep covers the exact
        hot rows and the packed bloom tail — hot verdicts come back with
        fp ≡ 0, tail verdicts bit-identical to a flat packed slab at the
        same blocks.  Result rows are hot-first: [0, H) hot, then the
        tail.  The hot set is a handful of metadata rows, so the sweep
        stays unsharded even under a mesh policy (the tail-sharded
        variant is a ROADMAP item)."""
        pol = self.policy
        out = ops._classify_hybrid(
            q, int(peers.local_version), peers.hot_meta, peers.hot_sums,
            peers.cells_u8, peers.base, bn=bn, bm=bm, interpret=interpret,
            use_autotune=pol.autotune)
        engine, blocks = _dispatch_label("hybrid")
        if peers.wide:
            # wide keys index TAIL slots; result rows shift by the hot
            # block, and the overlay must patch the shifted positions
            H = int(np.shape(peers.hot_meta)[0])
            widx = sorted(peers.wide)
            out = ops._overlay_wide_classify(
                out, q, [H + s for s in widx],
                jnp.asarray(np.stack([peers.wide[s] for s in widx])),
                interpret=interpret)
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
        tune0 = (autotune.CACHE_STATS["hit"], autotune.CACHE_STATS["miss"])
        with obs.trace.span("causal.pairs",
                            pack="slab" if isinstance(clocks, PackedSlab)
                            else "i32") as sp:
            res = self._pairs(clocks, cols, alive=alive,
                              alive_dev=alive_dev, engine=engine, bi=bi,
                              bj=bj, bm=bm, uniform_base=uniform_base,
                              interpret=interpret)
            self._record_dispatch("pairs", res, int(np.shape(res.le)[0]),
                                  sp, tune0)
        return res

    def _pairs(self, clocks, cols=None, *, alive=None, alive_dev=None,
               engine=None, bi=None, bj=None, bm=None, uniform_base=None,
               interpret=None) -> ComparisonMatrix:
        pol = self.policy
        engine = engine if engine is not None else pol.engine
        bi = bi if bi is not None else pol.bi
        bj = bj if bj is not None else pol.bj
        bm = bm if bm is not None else pol.bm
        interpret = interpret if interpret is not None else pol.interpret
        ops.LAST_DISPATCH.clear()
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
                                    bi, bj, bm, uniform_base, interpret)
        if alive is not None or alive_dev is not None:
            raise ValueError("alive masking needs a PackedSlab input")
        rows = _as_cells(clocks)
        if engine is None and not pol.pack:
            engine = "i32"
        cols_c = rows if cols is None else _as_cells(cols)
        out = ops._compare_matrix(
            rows, cols_c, engine=engine, bi=bi, bj=bj, bm=bm,
            interpret=interpret, use_autotune=pol.autotune)
        eng, blocks = _dispatch_label(engine or "auto")
        return ComparisonMatrix.from_dict(out, engine=eng, blocks=blocks)

    # ---- packed-slab assembly (compaction, promoted rims, masking) ----
    def _pairs_slab(self, slab: PackedSlab, alive, alive_dev, engine,
                    bi, bj, bm, uniform_base, interpret) -> ComparisonMatrix:
        pol = self.policy
        cap = slab.capacity
        alive = (np.ones(cap, bool) if alive is None
                 else np.asarray(alive, bool))
        aidx = np.flatnonzero(alive)
        kw = dict(engine=engine, bi=bi, bj=bj, bm=bm, interpret=interpret)
        if aidx.size == 0:
            false = jnp.zeros((cap, cap), bool)
            return ComparisonMatrix(
                le=false, ge=false, conc=false,
                fp=jnp.zeros((cap, cap), jnp.float32),
                row_sums=jnp.zeros((cap,), jnp.float32),
                col_sums=jnp.zeros((cap,), jnp.float32), engine="empty")
        if uniform_base is None:
            uniform_base = self._uniform_base(slab, alive)
        if pol.mesh is not None:
            # mesh placement only matters when the bulk is combined with
            # sharded masks/overlays below; the fully-alive packed fast
            # path returns it as-is, so the replicated strategy may skip
            # its output reshard
            bulk = ops._compare_matrix_packed_sharded(
                slab.cells_u8, slab.base, mesh=pol.mesh, axis=pol.axis,
                uniform_base=uniform_base, use_autotune=pol.autotune,
                mesh_outputs=not (aidx.size == cap and slab.packed), **kw)
            eng, blocks = _dispatch_label("ring_full")
            if aidx.size == cap and slab.packed:
                return ComparisonMatrix.from_dict(bulk, engine=eng,
                                                  blocks=blocks)
            if not slab.packed:
                # promoted rows: patch the O(P * A) int32 rim into the
                # bulk ON DEVICE — the [cap, cap] matrices stay sharded
                bulk = self._device_wide_overlay(slab, bulk, aidx, **kw)
                eng += "+wide_rim"
            # dead slots report nothing; masking is device-side too, so
            # a huge sharded fleet never materializes flags on host
            al = alive_dev if alive_dev is not None else jnp.asarray(alive)
            return ComparisonMatrix.from_dict(
                _mask_dead_pairs(bulk, al), engine=eng, blocks=blocks)
        if aidx.size == cap and slab.packed:
            out = ops._compare_matrix_packed(
                slab.cells_u8, slab.base, uniform_base=uniform_base,
                use_autotune=pol.autotune, **kw)
            eng, blocks = _dispatch_label("tri")
            return ComparisonMatrix.from_dict(out, engine=eng, blocks=blocks)
        if slab.packed:
            # gather the alive rows into a dense sub-slab: dead slots
            # cost no compute, results scatter back to full capacity
            jidx = jnp.asarray(aidx)
            sub = ops._compare_matrix_packed(
                jnp.take(slab.cells_u8, jidx, axis=0),
                jnp.take(slab.base, jidx),
                uniform_base=uniform_base, use_autotune=pol.autotune, **kw)
            eng, blocks = _dispatch_label("tri")
            return ComparisonMatrix.from_dict(
                _expand_alive(sub, jidx, cap), engine=eng, blocks=blocks)
        return self._host_pairs(slab, alive, aidx, **kw)

    @staticmethod
    def _uniform_base(slab: PackedSlab, alive: np.ndarray) -> bool | None:
        """Host-side base-uniformity probe over the alive rows; None
        (device probe in the impl) when no host base copy is carried."""
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
                  widx: np.ndarray, **kw) -> dict:
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
        # interpret/block-shape overrides carry over; a packed-engine
        # hint does not (it can't run on overflowed rows) — and since a
        # promoted row's span exceeds a byte BY DEFINITION, name the
        # int32 engine outright and skip the futile span probe
        rim_kw = {kk: v for kk, v in kw.items()
                  if kk in ("interpret", "bi", "bj", "bm") and v is not None}
        rim_kw["engine"] = "i32"
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
        return ops._compare_matrix(wide_rows, alive_i32,
                                   use_autotune=self.policy.autotune,
                                   **rim_kw)

    def _device_wide_overlay(self, slab: PackedSlab, bulk: dict,
                             aidx: np.ndarray, **kw) -> dict:
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
        rim = jax.device_put(self._wide_rim(slab, aidx, widx, **kw),
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
                    aidx: np.ndarray, **kw) -> ComparisonMatrix:
        """Unsharded sparse promoted-row assembly: packed engines over
        the still-packed alive rows plus the exact int32 rim for the
        promoted handful, stitched on host (the slab already lives on
        one device here — the sharded path patches on device instead,
        see ``_device_wide_overlay``).  fp is re-finalized from the
        corrected sums through the SAME jitted Eq. 3 expression the
        engines use (``ops.eq3_outer``), so values stay bit-identical
        to the single-device int32 fallback this replaces."""
        cap, m = slab.capacity, slab.m
        kw = {kk: v for kk, v in kw.items() if v is not None}
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
                uniform = None     # no host copy: let the impl probe
            sub = jax.device_get(ops._compare_matrix_packed(
                jnp.take(slab.cells_u8, jnp.asarray(pidx), axis=0),
                jnp.take(slab.base, jnp.asarray(pidx)),
                uniform_base=uniform,
                use_autotune=self.policy.autotune, **kw))
            eng, _ = _dispatch_label("tri")
            le[np.ix_(pidx, pidx)] = sub["a_le_b"]
            ge[np.ix_(pidx, pidx)] = sub["b_le_a"]
            sums[pidx] = sub["row_sums"]
        if widx.size:
            rim = jax.device_get(self._wide_rim(slab, aidx, widx, **kw))
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
