"""The profiler trace of a ``--trace 1`` run, reduced to numbers.

The window is the host span ``bench.window`` that the driver opens
around its measured window.  Device operations are the events on the
``XLA Ops`` line of each ``/device:*`` plane (a CPU trace has none; its
operations are the host events that carry an ``hlo_op`` stat, which is
what the tests record).  Busy time is the union of a device's operation
intervals inside the window; idle gaps are what is left, each labelled
by the benchmark's own ``bench.*`` host span that covers most of it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import tempfile

import numpy as np

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Op:
    name: str
    module: str
    start_ns: float
    end_ns: float
    device: str


@dataclasses.dataclass
class TraceSummary:
    window: tuple            # (start_ns, end_ns) of ``bench.window``
    ops: list                # every device Op that overlaps the window
    spans: list              # (name, start_ns, end_ns) of bench.* spans
    devices: list            # device plane names with operations

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Union of operation intervals inside the window, averaged
        over the devices that ran any."""
        if not self.devices:
            return 0.0
        return float(np.mean([_union(self._clipped(d)) / 1e9
                              for d in self.devices]))

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def _clipped(self, device: str) -> list:
        w0, w1 = self.window
        return [(max(o.start_ns, w0), min(o.end_ns, w1))
                for o in self.ops if o.device == device]

    def op_seconds(self, match) -> float:
        """Device seconds of the operations ``match(op)`` accepts, inside
        the window, summed over devices."""
        w0, w1 = self.window
        return sum(max(0.0, min(o.end_ns, w1) - max(o.start_ns, w0))
                   for o in self.ops if match(o)) / 1e9

    def top_ops(self, n: int = 10) -> list:
        totals: dict = {}
        w0, w1 = self.window
        for o in self.ops:
            d = max(0.0, min(o.end_ns, w1) - max(o.start_ns, w0))
            totals[o.name] = totals.get(o.name, 0.0) + d / 1e9
        return sorted(([k, v] for k, v in totals.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest idle gaps of the first device, each named by
        the bench span that covers most of it (else ``unannotated``)."""
        if not self.devices:
            return []
        w0, w1 = self.window
        iv = sorted(self._clipped(self.devices[0]))
        gaps, cursor = [], w0
        for s, e in iv:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if cursor < w1:
            gaps.append((cursor, w1))
        gaps.sort(key=lambda g: g[0] - g[1])
        spans = [s for s in self.spans if s[0] != WINDOW]
        names = [s[0] for s in spans]
        st = np.asarray([s[1] for s in spans], np.float64)
        en = np.asarray([s[2] for s in spans], np.float64)
        out = []
        for g0, g1 in gaps[:n]:
            label = "unannotated"
            if spans:
                cover = np.clip(np.minimum(en, g1) - np.maximum(st, g0), 0,
                                None)
                best = int(np.argmax(cover))
                if cover[best] >= 0.5 * (g1 - g0):
                    label = names[best]
            out.append([f"{label} at +{(g0 - w0) / 1e6:.3f} ms",
                        (g1 - g0) / 1e9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def _union(intervals: list) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def reduce_trace(path: str) -> TraceSummary:
    """Read one ``.xplane.pb`` file into a :class:`TraceSummary`."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops, spans, window = [], [], None
    device_planes = [p for p in data.planes if p.name.startswith("/device:")]
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                start, dur = float(ev.start_ns), float(ev.duration_ns)
                if is_device:
                    if line.name == OPS_LINE:
                        ops.append(Op(ev.name, str(_stats(ev).get(
                            "hlo_module", "")), start, start + dur,
                            plane.name))
                    continue
                if ev.name.startswith("bench."):
                    spans.append((ev.name, start, start + dur))
                    if ev.name == WINDOW:
                        window = (start, start + dur)
                elif not device_planes:
                    st = _stats(ev)
                    if "hlo_op" in st:
                        ops.append(Op(ev.name, str(st.get("hlo_module", "")),
                                      start, start + dur, "cpu"))
    if window is None:
        raise ValueError(f"no {WINDOW} span in {path}")
    w0, w1 = window
    ops = [o for o in ops if o.end_ns > w0 and o.start_ns < w1]
    devices = sorted({o.device for o in ops})
    return TraceSummary(window=window, ops=ops, spans=spans,
                        devices=devices)


class Profiler:
    """The JAX profiler around the measured window only (Python tracer
    off).  Drivers call ``start()`` as the window opens and ``stop()``
    once it has closed; ``summary`` then holds the reduced trace."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.summary: TraceSummary | None = None
        self._tmp = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self._tmp = tempfile.TemporaryDirectory(prefix="bench_trace_")
        jax.profiler.start_trace(self._tmp.name, profiler_options=opts)

    def stop(self) -> None:
        if self._tmp is None:
            return
        import jax
        jax.profiler.stop_trace()
        try:
            files = sorted(glob.glob(os.path.join(
                self._tmp.name, "**", "*.xplane.pb"), recursive=True))
            self.summary = reduce_trace(files[-1])
        finally:
            self._tmp.cleanup()
            self._tmp = None
