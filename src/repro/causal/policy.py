"""CausalPolicy: the one source of truth for causality decisions.

Before this existed, every caller re-decided three things by hand on
every call: which compare engine to run (packed triangle / full rect /
MXU thermometer / int32 fallback), what Eq. 3 confidence to demand, and
whether/how the peer slab is sharded over a mesh.  The policy bundles
those choices into one frozen dataclass that is threaded through
``ClockRuntime``, ``ClockRegistry``, gossip, serving and the launch
entry points, and consumed by ``CausalEngine`` — the single dispatch
front-door.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

from repro.sharding import FLEET_AXIS

__all__ = ["CausalPolicy"]

_ENGINES = (None, "tri", "full", "mxu", "i32")


@dataclasses.dataclass(frozen=True)
class CausalPolicy:
    """Dispatch + confidence policy for all causality comparisons.

    fp_threshold   Eq. 3 confidence gate every admit/merge decision uses
                   (``results.*.confident(policy.fp_threshold)``).
    engine         engine preference: None = measured auto-dispatch;
                   "tri" / "full" / "mxu" force a packed engine,
                   "i32" forces the legacy int32 kernel.
    pack           pack int32 inputs on the fly when the value span fits
                   a byte (False pins the int32 kernel path).
    mesh / axis    when a mesh is set, slab comparisons run sharded
                   (shard_map'd one-vs-many, ppermute all-pairs ring)
                   over ``axis``; results stay bit-identical to the
                   single-device engines for every shard count.
    bi/bj/bm/bn    explicit kernel block-shape overrides (None = let the
                   measured autotune table / per-backend defaults pick).
    autotune       consult the measured engine/block-shape table
                   (``kernels.autotune``); False = built-in defaults.
    interpret      force Pallas interpret mode (None = auto: interpret
                   off-TPU so the same kernel bodies run on CPU; see
                   ``kernels.template.resolve_interpret``, and
                   ``kernels.ops.DISPATCHES`` for what each call ran).
    observer       ``repro.obs.Observer`` riding the policy: every
                   consumer (engine, registry, gossip, runtime,
                   serving) instruments itself through it.  None (the
                   default) means null sinks — near-zero cost.
                   Observers hash/compare by identity, so the policy
                   stays hashable and usable as a cache key.
    """

    fp_threshold: float = 1e-4
    engine: Optional[str] = None
    pack: bool = True
    mesh: Any = None
    axis: str = FLEET_AXIS
    bi: Optional[int] = None
    bj: Optional[int] = None
    bm: Optional[int] = None
    bn: Optional[int] = None
    autotune: bool = True
    interpret: Optional[bool] = None
    observer: Any = None

    def __post_init__(self):
        if self.engine not in _ENGINES:
            raise ValueError(
                f"unknown engine {self.engine!r}; pick one of {_ENGINES}")

    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    @property
    def shards(self) -> int:
        return 1 if self.mesh is None else self.mesh.shape[self.axis]

    def merged(self, **overrides) -> "CausalPolicy":
        """Policy with the non-None overrides applied (per-call knobs)."""
        kept = {k: v for k, v in overrides.items() if v is not None}
        return dataclasses.replace(self, **kept) if kept else self

    def label(self) -> str:
        """Compact human/JSON descriptor (bench records, dashboards)."""
        parts = [f"fp<={self.fp_threshold:g}"]
        parts.append(f"engine={self.engine or 'auto'}")
        if not self.pack:
            parts.append("pack=off")
        if not self.autotune:
            parts.append("autotune=off")
        if self.mesh is not None:
            parts.append(f"shards={self.shards}:{self.axis}")
        blocks = {k: v for k, v in
                  (("bi", self.bi), ("bj", self.bj),
                   ("bm", self.bm), ("bn", self.bn)) if v is not None}
        if blocks:
            parts.append(",".join(f"{k}{v}" for k, v in blocks.items()))
        return " ".join(parts)
