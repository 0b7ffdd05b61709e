"""Checkpointing with bloom-clock lineage, async writes, elastic restore.

Layout per checkpoint:  <dir>/step_<N>/
  - state.npz        flattened pytree leaves (params / opt / clock / step)
  - manifest.json    step, run_id, clock snapshot (compressed §4 form),
                     param-table hash, mesh shape at save time

Fault-tolerance behaviors:
  - **async save**: the host snapshot (device_get) happens synchronously
    (cheap, it's a copy), the file write runs on a background thread;
    ``wait()`` drains before the next save (double buffering).
  - **atomic publish**: writes go to ``.tmp-step_<N>`` then os.rename.
  - **lineage-checked restore**: ``restore()`` hands back the stored clock;
    callers gate on ``ClockRuntime.admit_restore`` — restoring a checkpoint
    whose clock is CONCURRENT with the live run (fork/split brain) is
    refused at the runtime layer.
  - **elastic reshard**: restore is mesh-agnostic (leaves land on host,
    then ``jax.device_put`` with the *new* mesh's shardings), so scale-up/
    scale-down = restore under a different mesh. The bloom clock needs no
    resize on membership change — the paper's core advantage.
  - **GC**: keep the newest ``keep`` checkpoints.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Callable, Optional

import jax
import numpy as np

__all__ = ["CheckpointManager"]


def _flatten(tree) -> dict:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        flat[key] = np.asarray(leaf)
    return flat


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, run_id: str = "run0"):
        self.dir = directory
        self.keep = keep
        self.run_id = run_id
        self._thread: Optional[threading.Thread] = None
        os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------------ save
    def save(self, step: int, state, clock_snapshot: dict,
             extra: Optional[dict] = None, block: bool = False) -> str:
        """Snapshot now, write async. Returns the final path."""
        self.wait()  # double buffer: at most one write in flight
        state_host = jax.tree.map(lambda x: np.asarray(x), state)
        flat = _flatten(state_host)
        manifest = {
            "step": int(step),
            "run_id": self.run_id,
            "clock": {
                "cells": [int(v) for v in clock_snapshot["cells"]],
                "base": int(clock_snapshot["base"]),
                "k": int(clock_snapshot["k"]),
            },
            "n_leaves": len(flat),
            **(extra or {}),
        }
        final = os.path.join(self.dir, f"step_{step}")
        tmp = os.path.join(self.dir, f".tmp-step_{step}")

        def _write():
            os.makedirs(tmp, exist_ok=True)
            np.savez(os.path.join(tmp, "state.npz"), **flat)
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)
            self._gc()

        if block:
            _write()
        else:
            self._thread = threading.Thread(target=_write, daemon=True)
            self._thread.start()
        return final

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self) -> None:
        steps = sorted(self.list_steps())
        for s in steps[: -self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.dir, f"step_{s}"), ignore_errors=True)

    # --------------------------------------------------------------- restore
    def list_steps(self) -> list:
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("step_"):
                out.append(int(name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.list_steps()
        return steps[-1] if steps else None

    def clock_manifests(self) -> list:
        """[(step, manifest)] for every checkpoint, sorted by step.

        Reads only the manifest.json files (clock snapshots are a few KB
        in §4 wire form) — this is what ``ClockRuntime.
        classify_checkpoints`` feeds to one ``causal.classify`` call to
        lineage-check a whole directory without touching state tensors.
        """
        self.wait()
        out = []
        for step in self.list_steps():
            path = os.path.join(self.dir, f"step_{step}", "manifest.json")
            with open(path) as f:
                out.append((step, json.load(f)))
        return out

    def restore(self, step: Optional[int] = None,
                target_structure=None, shardings=None):
        """Returns (state, manifest). With ``shardings`` (a pytree matching
        the state), leaves are device_put with those shardings — this is the
        elastic-reshard path (any mesh shape, any host count)."""
        self.wait()
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        path = os.path.join(self.dir, f"step_{step}")
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        flat = dict(np.load(os.path.join(path, "state.npz")))
        if target_structure is None:
            state = flat
        else:
            leaves_paths = jax.tree_util.tree_flatten_with_path(target_structure)
            keys = ["/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in kp)
                    for kp, _ in leaves_paths[0]]
            missing = [k for k in keys if k not in flat]
            if missing:
                raise KeyError(f"checkpoint missing leaves: {missing[:5]}...")
            leaves = [flat[k] for k in keys]
            state = jax.tree_util.tree_unflatten(leaves_paths[1], leaves)
        if shardings is not None:
            state = jax.tree.map(
                lambda x, s: jax.device_put(x, s), state, shardings)
        return state, manifest
