"""Compile the main-path Pallas kernels for a TPU v5e that is described,
not attached.

Interpret-mode tests cannot see what the chip's compiler refuses: block
shapes off the (8, 128) tiling, ops with no Mosaic lowering, bool
relayouts, kernels over the VMEM limit.  Each case here lowers one
kernel through the same ops-layer runner the TPU dispatch uses
(``interpret=False``), at the blocks ``CausalEngine`` resolves there and
at real widths, and compiles it for one chip of a described ``v5e:2x2``
topology.  Nothing runs.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler library, and every
test worker imports this file.
"""
import os
import re
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.causal import CausalEngine, CausalPolicy
from repro.kernels import ops

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench.kernels import ovm  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it off
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            jax.config.update("jax_enable_compilation_cache", enabled)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)


#: the blocks ``CausalEngine`` resolves on the chip with no autotune
#: entry (every lookup on v5e misses the interpret-only table)
OVM = {"bn": 512, "bm": 512}
MAT = {"bi": 128, "bj": 128, "bm": 512}
MXU = {"bi": 128, "bj": 128, "bm": 128}


def _one_vs_many(S, pack):
    N, m = (1 << 20, 256) if pack == "u8" else (65536, 256)
    if pack == "u8":
        return (lambda q, p, b: ops.classify_packed(q, p, b, **OVM,
                                                    interpret=False),
                (S((m,), jnp.int32), S((N, m), jnp.uint8),
                 S((N,), jnp.int32)))
    return (lambda q, p: ops.classify_i32(q, p, **OVM, interpret=False),
            (S((m,), jnp.int32), S((N, m), jnp.int32)))


def _matrix(S, engine, n=1024, m=1024):
    u8 = (S((n, m), jnp.uint8), S((n,), jnp.int32))
    if engine == "tri":
        return (lambda c, b: ops.compare_packed(
            c, b, engine="tri", **MAT, uniform_base=False,
            interpret=False), u8)
    if engine in ("full", "mxu"):
        return (lambda r, b, c, cb: ops.compare_packed(
            r, b, c, cb, engine=engine, **(MXU if engine == "mxu" else MAT),
            uniform_base=False, interpret=False, bounds=(0, 64)), u8 + u8)
    return (lambda r, c: ops.compare_i32(r, c, **MAT, interpret=False),
            (S((n, m), jnp.int32), S((n, m), jnp.int32)))


def _hybrid(S, H=4096, T=65536, m=512):
    def fn(q, meta, hot_sums, tail, base):
        return ops.classify_hybrid(q, 7, meta, hot_sums, tail, base, **OVM,
                                   interpret=False)[0]
    return fn, (S((m,), jnp.int32), S((2, H), jnp.int32),
                S((H,), jnp.float32), S((T, m), jnp.uint8),
                S((T,), jnp.int32))


CASES = {
    "one_vs_many_u8": lambda S: _one_vs_many(S, "u8"),
    "one_vs_many_i32": lambda S: _one_vs_many(S, "i32"),
    "tri_u8": lambda S: _matrix(S, "tri"),
    "rect_u8": lambda S: _matrix(S, "full"),
    "rect_i32_stats": lambda S: _matrix(S, "i32"),
    "mxu": lambda S: _matrix(S, "mxu"),
    "hybrid": _hybrid,
    "merge_compare": lambda S: (
        lambda a, b: ops.merge_compare(a, b, interpret=False),
        (S((1024, 1024), jnp.int32), S((1024, 1024), jnp.int32))),
    "tick": lambda S: (
        lambda c, hi, lo: ops.tick(c, hi, lo, k=4, interpret=False),
        (S((1024, 1024), jnp.int32), S((1024, 16), jnp.uint32),
         S((1024, 16), jnp.uint32))),
}


#: the stable ``pallas_call`` name that each case's kernel gives its
#: HLO operation, and so its event in the device trace
NAMES = {
    "one_vs_many_u8": "bloom_one_vs_many_u8",
    "one_vs_many_i32": "bloom_one_vs_many_i32",
    "tri_u8": "bloom_tri_u8",
    "rect_u8": "bloom_rect_u8",
    "rect_i32_stats": "bloom_rect_i32",
    "mxu": "bloom_mxu_u8",
    "hybrid": "bloom_hybrid_u8",
    "merge_compare": "bloom_merge_compare",
    "tick": "bloom_tick",
}


def _compiled_text(one_chip, cases, case) -> str:
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = cases[case](S)
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_calls(one_chip, case, cases=CASES) -> list:
    """The HLO lines of the Pallas calls the case compiles to."""
    return [line for line in _compiled_text(one_chip, cases, case)
            .splitlines() if "tpu_custom_call" in line]


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    calls = _kernel_calls(one_chip, case)
    assert calls
    for line in calls:
        assert re.match(rf"\s*(ROOT )?%{NAMES[case]}(\.\d+)? = ", line), line


@pytest.mark.parametrize("case", ["one_vs_many_u8", "one_vs_many_i32"])
def test_ovm_trace_rule_matches_the_packed_kernel_only(one_chip, case):
    """``bench/kernels/ovm.py`` picks the packed one-vs-many kernel out of
    a device trace by its operation's text; the int32 kernel, which runs
    the rim, must not match."""
    (line,) = _kernel_calls(one_chip, case)
    assert ovm.is_kernel(types.SimpleNamespace(name=line)) == (
        case == "one_vs_many_u8")


@pytest.mark.parametrize("op,engine,blocks", [
    ("one_vs_many", None, OVM), ("hybrid", None, OVM),
    ("matrix", "tri", MAT), ("matrix", "mxu", MXU)])
def test_chip_blocks_are_the_compiled_ones(op, engine, blocks):
    """The blocks compiled above are the ones the engine resolves for
    the chip when the table has no entry for it."""
    eng = CausalEngine(CausalPolicy(autotune=False))
    assert eng.blocks(op, 1 << 20, 4096, 1024, interpret=False,
                      engine=engine) == blocks


#: the two cells' sweeps at full size: the fleet registry's one-vs-many
#: over 1,048,576 rows, and the session store's hybrid over 65,536 hot
#: rows and a 983,040-row tail, both at m = 1,024
SWEEPS = {
    "one_vs_many_u8": lambda S: (
        lambda q, p, b: ops.classify_packed(q, p, b, **OVM,
                                            interpret=False),
        (S((1024,), jnp.int32), S((1 << 20, 1024), jnp.uint8),
         S((1 << 20,), jnp.int32))),
    "hybrid": lambda S: _hybrid(S, H=65536, T=983040, m=1024),
}

#: a 2-D shape of 65,536 or more rows whose minor dimension is 1 or 2:
#: on the chip such a column is padded to 128 lanes a row
_NARROW = re.compile(r"\b[a-z]+\d*\[(\d+),([12])\]")


def _narrow_columns(text: str) -> list:
    return [m.group(0) for m in _NARROW.finditer(text)
            if int(m.group(1)) >= 65536]


@pytest.mark.parametrize("case", sorted(SWEEPS))
def test_sweep_has_no_lane_padded_columns(one_chip, case):
    """Per-row vectors cross the kernel boundary, and every XLA
    operation around it, as dense rows: no base, metadata, flag, sum or
    Eq. 3 column is laid out one row per 128 lanes."""
    text = _compiled_text(one_chip, SWEEPS, case)
    assert not _narrow_columns(text), sorted(set(_narrow_columns(text)))
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1, calls
    assert re.match(rf"\s*(ROOT )?%{NAMES[case]}(\.\d+)? = ", calls[0])
    assert not _narrow_columns(calls[0]), calls[0]
