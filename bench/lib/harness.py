"""Loading by name, the chip check, compile counting and the result line."""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path
from typing import Callable

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
SRC = ROOT / "src"
CACHE_DIR = ROOT / ".jax_cache"
#: the longest window any run may ask for; data that depends on the
#: window length is sized for this, so every --seconds sees the same data
MAX_SECONDS = 51


class SetupError(RuntimeError):
    """The run cannot start: no chip, a missing file, a bad name."""


@dataclasses.dataclass
class Outcome:
    """What a driver hands back once its window has closed and the
    program's state is freed."""

    attempted: int
    failed: int
    e2e: dict                 # end-to-end metric name -> value
    ctx: dict                 # what the per-layer readers read
    info: dict                # printed on an earlier line
    check: Callable[..., list]  # (variant) -> [(name, value, limit)]
    setup_s: float


def passed(checks: list) -> bool:
    return all(value <= limit for _, value, limit in checks)


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SetupError(f"no BENCHMARK.json at {root}")
    return read_json(path)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SetupError(f"no workload named {name!r} in BENCHMARK.json")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return read_json(root / cfg["file"])
    raise SetupError(f"no config named {name!r} in BENCHMARK.json")


def load_traffic(name: str, root: Path = ROOT) -> dict:
    path = root / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SetupError(f"no traffic file bench/traffic/{name}.json")
    return read_json(path)


def load_driver(name: str):
    """``bench/drivers/<name>.py``: a module with ``run(...)``."""
    if not (BENCH_DIR / "drivers" / f"{name}.py").is_file():
        raise SetupError(f"no driver bench/drivers/{name}.py")
    return importlib.import_module(f"bench.drivers.{name}")


def load_file_module(path: Path):
    """Import a file whose name need not be an identifier (metric names
    carry dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` that this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def read_per_layer(metrics: list[dict], ctx: dict,
                   root: Path = ROOT) -> dict:
    """Run each metric's reader; a reader that finds nothing returns
    None and the metric is left out of the line."""
    out = {}
    for m in metrics:
        mod = load_file_module(root / "bench" / "metrics" / f"{m['name']}.py")
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def peaks_for(kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = read_json(BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise SetupError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def require_chips(n: int) -> list:
    """The TPU devices of this machine; SetupError when there is no TPU
    or fewer than ``n`` chips (there is no CPU fallback)."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SetupError(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < n:
        raise SetupError(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class HostEvents:
    """Backend compiles (the listener is the one the bring-up smoke run
    used) and garbage-collector passes seen by this process, so a window
    can tell a host stall of its own from the program's."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.count = 0
        self.seconds = 0.0
        self.gc_passes: list = []          # (start, seconds)
        self._gc_start = None

        def on_event(event, duration, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._gc_start = now
        elif self._gc_start is not None:
            self.gc_passes.append((self._gc_start, now - self._gc_start))
            self._gc_start = None

    def mark(self) -> tuple:
        return self.count, time.perf_counter()

    def since(self, mark: tuple) -> dict:
        """Compiles and collector time since ``mark`` (the window's)."""
        count, t0 = mark
        passes = [d for t, d in self.gc_passes if t >= t0]
        return {"compiles_in_window": self.count - count,
                "gc_passes_in_window": len(passes),
                "gc_s_in_window": sum(passes),
                "gc_longest_ms_in_window": max(passes, default=0.0) * 1e3}

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)


def device_info(devices, count: int) -> dict:
    used = devices[:count]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": count, "memory_peak_bytes": peak}


def interpreted_kernels() -> list:
    """Ops that ran in the Pallas interpreter (must be none on a chip)."""
    from repro.kernels import ops
    return sorted({op for op, _, interp in ops.DISPATCHES if interp})


def finite(x: float) -> float:
    if not math.isfinite(x):
        raise ValueError(f"non-finite metric value {x}")
    return x


def print_checks(checks: list) -> None:
    """Each number compared beside its limit, as the last lines of
    standard error."""
    for name, value, limit in checks:
        print(f"check {name} = {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: list, breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit in checks}
    return json.dumps(out)
