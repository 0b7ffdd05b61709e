"""Anti-entropy gossip: config, report, and the loopback round.

One round = what a node does when it wakes up and reconciles with its
view of the fleet.  The protocol itself — digest exchange → classify
via the ``CausalEngine`` → delta pull of §4 wire rows → one batched
union merge → push-back — lives in ``fleet.transport.session`` and is
parameterized by a :class:`~repro.fleet.transport.Transport`:

- ``LoopbackTransport``        the local registry slab is the fleet
  (this module's ``gossip_round`` — the original single-process round,
  bit-identical masks / merged cells / Eq. 3 fp bits);
- ``MeshCollectiveTransport``  a mesh-sharded registry whose digest
  exchange runs as a ``ppermute`` ring over the fleet axis — row shards
  never round-trip through the host;
- ``SocketTransport``          real processes exchanging length-prefixed
  ``core.wire`` frames over TCP.

The round's policy, on [N] host vectors: FORKED peers are quarantined
(their events diverged from ours — merging would launder a causality
violation); stragglers (clock-sum gap above ``straggler_gap`` below the
alive median) are skipped this round, not quarantined; remaining
comparable peers with Eq. 3 fp within the policy gate are accepted and
merged in ONE batched union (paper §3 receive rule fleet-wide).  With
push-back, the union ships back to every accepted peer in §4 wire form;
``GossipReport`` records the MEASURED frame bytes of each phase, not a
model estimate.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

from repro.causal import CausalPolicy
from repro.core import clock as bc
from repro.fleet import registry as reg

__all__ = ["GossipConfig", "GossipReport", "gossip_round"]

_FP_DEFAULT = 1e-4


@dataclasses.dataclass(frozen=True)
class GossipConfig:
    straggler_gap: float = 64.0   # clock-sum ticks below alive median
    push_back: bool = True        # write the union into accepted rows
    # the one source of truth when set: rounds gate on
    # ``policy.fp_threshold``, so a runtime threads its CausalPolicy
    # straight through gossip
    policy: Optional[CausalPolicy] = None
    # instrumentation override for this config; sessions fall back to
    # ``policy.observer`` and then the registry's policy when None
    observer: Any = None
    # self-stabilization: verify every alive registry row against its
    # recorded CRC at the top of each session; corrupted rows are
    # quarantined and (on a non-authoritative fabric) repaired by
    # forcing the delta phase to re-pull them from any peer whose
    # digest covers the row
    verify_rows: bool = False
    # paper §3 pure receive rule: merge FORKED (concurrent) peers too
    # instead of quarantining them.  Quarantine treats a fork as replica
    # divergence to investigate; a gossip fleet whose nodes legitimately
    # tick concurrently (the chaos/convergence harness) needs forks to
    # MERGE or concurrent peers could never reconverge.
    merge_forked: bool = False

    @property
    def fp_gate(self) -> float:
        if self.policy is not None:
            return self.policy.fp_threshold
        return _FP_DEFAULT


@dataclasses.dataclass
class GossipReport:
    """Outcome masks of one round (numpy, [capacity]) + measured wire."""

    accepted: np.ndarray          # merged this round
    quarantined: np.ndarray       # FORKED -> excluded until resolved
    stragglers: np.ndarray        # skipped this round (not quarantined)
    unconfident: np.ndarray       # comparable but fp above threshold
    view: reg.FleetView           # the classification the round acted on
    pushback_bytes: int = 0       # MEASURED outbound frame bytes (§4 form)
    digest_bytes: int = 0         # MEASURED inbound digest-exchange bytes
    delta_bytes: int = 0          # MEASURED inbound delta-frame bytes
    transport: str = "loopback"   # fabric the session ran over
    shards: int = 1               # device shards the registry slab spans
    unreachable: tuple = ()       # peers skipped mid-session (socket)
    rejected: tuple = ()          # peers whose pulled frame failed decode
    corrupted: tuple = ()         # rows that failed the CRC integrity check
    repaired: tuple = ()          # corrupted rows re-pulled this session

    @property
    def n_accepted(self) -> int:
        return int(self.accepted.sum())

    @property
    def wire_bytes(self) -> int:
        """Total measured bytes this round moved over the fabric."""
        return self.digest_bytes + self.delta_bytes + self.pushback_bytes

    def summary(self) -> str:
        return (
            f"accepted={int(self.accepted.sum())} "
            f"quarantined={int(self.quarantined.sum())} "
            f"stragglers={int(self.stragglers.sum())} "
            f"unconfident={int(self.unconfident.sum())} "
            f"alive={int(self.view.alive.sum())} "
            f"wire={self.wire_bytes}B[{self.transport}]"
            + (f" unreachable={len(self.unreachable)}"
               if self.unreachable else "")
            + (f" rejected={len(self.rejected)}" if self.rejected else "")
            + (f" corrupted={len(self.corrupted)}"
               f" repaired={len(self.repaired)}" if self.corrupted else "")
        )


def gossip_round(
    registry: reg.ClockRegistry,
    local: bc.BloomClock,
    cfg: GossipConfig = GossipConfig(),
) -> tuple[bc.BloomClock, GossipReport]:
    """One anti-entropy round over the LOCAL registry slab.

    Loopback session: identical decision math to every other transport,
    with the peer rows already in the slab (no digest/delta traffic).
    Returns (merged local clock, report).
    """
    from repro.fleet.transport import LoopbackTransport
    from repro.fleet.transport.session import anti_entropy_session
    return anti_entropy_session(registry, local, LoopbackTransport(registry),
                                cfg)
