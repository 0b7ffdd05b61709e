"""Pallas kernel layer for the bloom-clock hot paths.

- ``bloom_tick``     batched event recording (scatter-add per probe)
- ``bloom_compare``  fused pairwise merge + compare + Eq. 3 fp
- ``bloom_matrix``   one-vs-many and N x N comparison engines, including
                     the packed-u8 triangle sweep and the MXU
                     (dot_general thermometer) dominance reduction
- ``pack``           quantized slab layout: u8 window residuals + base
- ``autotune``       measured block-shape/engine table ``CausalEngine`` reads
- ``ops``            kernel runners (pad, run, crop): they run what the
                     ``repro.causal.CausalEngine`` front-door chose
- ``ref``            pure-jnp oracles for tests
"""
