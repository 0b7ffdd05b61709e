"""Unified causality API — the public surface of the reproduction.

The paper's contract ("compare two timestamps, get a partial order plus
an Eq. 3 confidence") behind one policy, two verbs and three typed
results:

    from repro import causal

    policy = causal.CausalPolicy(fp_threshold=1e-4)
    engine = causal.CausalEngine(policy)

    engine.classify(query, peers)   # one-vs-many -> ClassifyResult
    engine.pairs(clocks)            # all-pairs   -> ComparisonMatrix
    causal.compare(a, b)            # pairwise    -> Comparison

Every compare engine (int32 fallback, packed u8 triangle/rectangle, MXU
thermometer, promoted-row overlay, shard_map'd sharded paths) sits
behind the two verbs; results carry ``.before() / .after() /
.concurrent() / .confident(threshold)`` so the Eq. 3 gate is applied
one way everywhere.  ``CausalEngine`` is the one layer that chooses a
kernel; ``kernels.ops`` only runs it.
"""
from repro.causal.engine import CausalEngine, PackedSlab, compare
from repro.causal.policy import CausalPolicy
from repro.causal.results import ClassifyResult, Comparison, ComparisonMatrix

__all__ = [
    "CausalEngine",
    "CausalPolicy",
    "PackedSlab",
    "Comparison",
    "ComparisonMatrix",
    "ClassifyResult",
    "compare",
]
