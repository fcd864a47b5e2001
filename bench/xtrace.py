"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read: device op intervals (name, start, duration), the busy union over a
window, and the benchmark's own host spans (``bench.*``).

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per operation run, the ``XLA Modules`` line one per
program run (``jit_<function>(<hash>)``).  An op event's name is its
HLO instruction (``%fusion.12 = bf16[...] fusion(...)``); control flow
(``while``, ``conditional``, ``call``) is an event too, spanning the ops
of its body, so op events nest.  The host
plane ``/host:CPU`` holds the spans the harness opens with
``jax.profiler.TraceAnnotation``.  All times are nanoseconds on one
clock.
"""
from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
CONTROL = re.compile(r"^%?(while|conditional|call)[.\s]")


def op_name(event_name: str) -> str:
    """``%fused_sampled_dw.41 = f32[...] custom-call(...)`` ->
    ``fused_sampled_dw``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def options():
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 2
    return o


def xplane_file(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {files}")
    return files[0]


class Trace:
    """``ops[d]``/``modules[d]``: [(name, start_ns, dur_ns)] of device d;
    ``host``: [(name, start_ns, dur_ns)] of the bench spans."""

    def __init__(self, ops, modules, host):
        self.ops, self.modules, self.host = ops, modules, host
        if not host:
            raise RuntimeError("no bench.* host span in the trace")
        # the window: the harness's spans and every device op in them
        # (device and host clocks agree to about a millisecond)
        ev = [(s, s + d) for _, s, d in host] + [
            (s, s + d) for rows in ops.values() for _, s, d in rows]
        self.t0 = min(a for a, _ in ev)
        self.t1 = max(b for _, b in ev)

    @classmethod
    def from_xspace(cls, pd):
        ops, modules, host = {}, {}, []
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                d = int(m.group(1))
                for line in plane.lines:
                    if line.name not in (OPS_LINE, MODULES_LINE):
                        continue
                    rows = [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
                    (ops if line.name == OPS_LINE else modules)[d] = rows
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(HOST_PREFIX):
                            host.append((e.name, e.start_ns,
                                         e.duration_ns))
        return cls(ops, modules, host)

    @classmethod
    def load(cls, log_dir: str):
        from jax.profiler import ProfileData
        return cls.from_xspace(ProfileData.from_file(xplane_file(log_dir)))

    # -- reductions ---------------------------------------------------

    @property
    def devices(self):
        return sorted(self.ops)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy_intervals(self, d):
        """Union of device d's op intervals within the window."""
        iv = sorted((max(s, self.t0), min(s + du, self.t1))
                    for _, s, du in self.ops.get(d, ()))
        out = []
        for a, b in iv:
            if b <= a:
                continue
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return out

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices in the trace."""
        devs = self.devices
        if not devs:
            return 0.0
        return sum(sum(b - a for a, b in self.busy_intervals(d))
                   for d in devs) / len(devs) / 1e9

    def op_seconds(self, pattern: str) -> float:
        """Seconds of the device ops whose instruction name matches
        ``pattern`` (a regex), summed over devices."""
        rx = re.compile(pattern)
        return sum(du for d in self.devices for n, _, du in self.ops[d]
                   if rx.search(op_name(n))) / 1e9

    def module_seconds(self, pattern: str):
        """(seconds, runs) of device programs whose name matches."""
        rx = re.compile(pattern)
        hits = [du for d in self.modules for n, _, du in self.modules[d]
                if rx.search(n)]
        return sum(hits) / 1e9, len(hits)

    def top_ops(self, n=10):
        """[[op name, seconds]] of the leaf ops (control flow left out,
        its body's ops count), largest first, averaged over devices."""
        tot = collections.Counter()
        for d in self.devices:
            for name, _, du in self.ops[d]:
                if not CONTROL.match(name):
                    tot[op_name(name)] += du
        k = max(1, len(self.devices))
        return [[name, v / k / 1e9] for name, v in tot.most_common(n)]

    def idle_gaps(self, n=10):
        """Idle device time in the window, by the innermost bench span
        the host was in at the middle of each gap, largest first."""
        tot = collections.Counter()
        for d in self.devices:
            busy = self.busy_intervals(d)
            edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    tot[self._host_at((a + b) // 2)] += b - a
        k = max(1, len(self.devices))
        return [[name, v / k / 1e9] for name, v in tot.most_common(n)]

    def _host_at(self, t):
        best = None
        for name, s, du in self.host:
            if s <= t < s + du and (best is None or du < best[1]):
                best = (name, du)
        return best[0] if best else "outside bench spans"
