"""The traced run: JAX's profiler over the last part of the window, and
the reduction of its trace to what the per-layer readers need.

Device planes are ``/device:TPU:<n>``.  Each operation the device ran is
an event on the plane's ``XLA Ops`` line, named by its HLO instruction
(``%fusion.12 = bf16[...] fusion(...)``); a Pallas kernel's custom call
takes the kernel function's name (``%aaren_scan.4``, ``%aaren_scan_bwd.11``).
A ``while`` loop's event spans the events of its body.  Each execution of a
jitted program is an event on the ``XLA Modules`` line (not read here).  Host spans
(``TraceAnnotation``: the program's ``engine.*`` when ``REPRO_TRACE`` is
on, and this harness's ``bench.*``) are events of the host plane, on the
same clock as the device's.
"""

from __future__ import annotations

import glob
import re
import shutil
import tempfile
import time
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_SPAN = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")   # engine.step, bench.submit
TOP = 10


class Tracer:
    """Records the last ``length`` seconds of the window.  Stopping the
    profiler writes the trace out, which takes seconds, so it is stopped
    only once the window has closed."""

    def __init__(self, length: float):
        self.length = length
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        self.start_at = 0.0
        self.t_on = self.t_off = None

    def arm(self, seconds: float) -> None:
        self.start_at = max(0.0, seconds - self.length)

    def poll(self, elapsed: float) -> None:
        import jax

        if self.t_on is None and elapsed >= self.start_at:
            jax.profiler.start_trace(self.dir)
            self.t_on = time.perf_counter()

    def stop(self) -> None:
        import jax

        if self.t_on is not None and self.t_off is None:
            self.t_off = time.perf_counter()
            jax.profiler.stop_trace()

    def reduce(self) -> dict | None:
        if self.t_on is None or self.t_off is None:
            return None
        files = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
        if not files:
            return None
        out = reduce_file(files[0])
        out["window_s"] = self.t_off - self.t_on
        return out

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def _union(intervals: list) -> list:
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def reduce_file(path: str) -> dict:
    """Device events, host spans and busy time from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    spans: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if HOST_SPAN.match(e.name):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    busy = {d: _union([(s, e) for s, e, _ in ops])
            for d, ops in devices.items()}
    busy_s = {d: sum(e - s for s, e in iv) * 1e-9 for d, iv in busy.items()}
    return {"devices": devices, "busy": busy, "busy_s_by_device": busy_s,
            "busy_s": (sum(busy_s.values()) / len(busy_s)) if busy_s else 0.0,
            "spans": spans}


def op_name(event_name: str) -> str:
    """``%fusion.12`` of ``%fusion.12 = bf16[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0]


CONTAINERS = re.compile(r"^%(while|conditional|call)[.\d]*$")


def short(event_name: str) -> str:
    """The instruction's name and result type, without layouts."""
    head, _, rest = event_name.partition(" = ")
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    return f"{head} = {rest}"[:100] if rest else head


def breakdown(trace: dict) -> dict:
    """Top device operations by time (averaged over devices; a loop's own
    event is left out, its body's operations count), and the longest idle
    gaps of the first device, each labelled by the host span open at its
    middle, or else by the host span that ended last before it."""
    per_op: dict = defaultdict(float)
    for ops in trace["devices"].values():
        for s, e, name in ops:
            if not CONTAINERS.match(op_name(name)):
                per_op[short(name)] += (e - s) * 1e-9
    n_dev = max(len(trace["devices"]), 1)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = []
    if trace["busy"]:
        iv = trace["busy"][sorted(trace["busy"])[0]]
        gaps = [(iv[k][1], iv[k + 1][0]) for k in range(len(iv) - 1)]
        gaps.sort(key=lambda g: g[0] - g[1])
        gaps = gaps[:TOP]
    spans = sorted(trace["spans"], key=lambda sp: sp[1] - sp[0])
    by_end = sorted(trace["spans"], key=lambda sp: sp[1])

    def label(gs, ge):
        mid = (gs + ge) / 2
        for s, e, name in spans:      # innermost (shortest) span first
            if s <= mid <= e:
                return name
        before = [name for s, e, name in by_end if e <= mid]
        return f"after {before[-1]}" if before else "no host span"

    return {"device_ops": [[n, v / n_dev] for n, v in top_ops],
            "idle_gaps": [[label(s, e), (e - s) * 1e-9] for s, e in gaps]}
