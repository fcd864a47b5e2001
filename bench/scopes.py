"""The program's named layers in a training trace: each device op's JAX
name stack, and the program's own host spans (``repro.*``).

The profiler keeps each device op's name stack as the ``tf_op`` stat of
the op's event metadata in the device plane, e.g.
``jit(train_step)/transpose(jvp(forward))/while/body/wtacrs_dw/dot_general:``.
``jax.profiler.ProfileData`` does not expose metadata stats, so this
module reads the ``.xplane.pb`` protobuf itself: a wire-format walk over
the planes' ``event_metadata`` and ``stat_metadata`` that steps over the
device planes' ``lines`` (most of the file's bytes).  The field numbers
are those of ``tensorflow/tsl/profiler/protobuf/xplane.proto``.  Device
op times come from the ``xtrace.Trace`` the harness already reduced,
joined on the op's event name, which is its metadata name.

A name stack is split at ``/``; a component names a scope bare or inside
transformations (``jvp(forward)``).  ``forward`` is the loss under
``value_and_grad``; ``backward`` is every op under
``transpose(jvp(forward))``, rematerialized recompute included.  A
fusion counts wholly to its own (its root's) name stack.  Control flow
is left out, as in ``xtrace.Trace.top_ops``.  On a program without these
scopes every sum is 0 and the readers report nothing.
"""
from __future__ import annotations

import collections
import json
import re
import sys
import time

import spec
import xtrace

# xplane.proto field numbers
XSPACE_PLANES = 1
PLANE_NAME, PLANE_LINES, PLANE_EVENT_MD, PLANE_STAT_MD = 2, 3, 4, 5
LINE_TIMESTAMP_NS, LINE_EVENTS = 3, 4
EVENT_MD_ID, EVENT_OFFSET_PS, EVENT_DURATION_PS = 1, 2, 3
STAT_MD_ID, STAT_STR, STAT_REF = 1, 5, 7
EMD_NAME, EMD_STATS = 2, 5
SMD_ID, SMD_NAME = 1, 2
MAP_KEY, MAP_VALUE = 1, 2

TF_OP = "tf_op"
HOST_PLANE = "/host:CPU"
HOST_PREFIX = "repro."
STEP_SPANS = "repro.step."
TRAIN_MODULE = re.compile(r"^jit_train_step\b")

FORWARD, BACKWARD, OPTIMIZER = "forward", "backward", "optimizer"
SCOPES = (FORWARD, OPTIMIZER, "wtacrs_plan", "wtacrs_dw", "attention",
          "mlstm_scan", "slstm_scan")
STEP_PARTS = (FORWARD, BACKWARD, OPTIMIZER)
_WRAP = re.compile(r"^(?:[\w.<>-]+\()+|\)+$")


# -- protobuf wire format ----------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i, end):
    """(field number, value) of a message's fields in buf[i:end]: a
    varint's integer, a length-delimited field's (start, end)."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _str(buf, r):
    return bytes(buf[r[0]:r[1]]).decode("utf-8", "replace")


def _plane(buf, r):
    """(name, [line ranges], {event id: (name, [stat ranges])},
    {stat id: name}) of one XPlane."""
    name, lines, ev_md, st_md = "", [], {}, {}
    for f, v in _fields(buf, *r):
        if f == PLANE_NAME:
            name = _str(buf, v)
        elif f == PLANE_LINES:
            lines.append(v)
        elif f in (PLANE_EVENT_MD, PLANE_STAT_MD):
            key, val = 0, None
            for mf, mv in _fields(buf, *v):
                if mf == MAP_KEY:
                    key = mv
                elif mf == MAP_VALUE:
                    val = mv
            if val is None:
                continue
            if f == PLANE_STAT_MD:
                st_md[key] = next((_str(buf, sv) for sf, sv
                                   in _fields(buf, *val) if sf == SMD_NAME),
                                  "")
            else:
                ev_name, stats = "", []
                for ef, ev in _fields(buf, *val):
                    if ef == EMD_NAME:
                        ev_name = _str(buf, ev)
                    elif ef == EMD_STATS:
                        stats.append(ev)
                ev_md[key] = (ev_name, stats)
    return name, lines, ev_md, st_md


def _stat_str(buf, r, st_md):
    """(stat name, string value) of one XStat; a ``ref_value`` names
    the stat metadata that holds the string."""
    sid, val = 0, None
    for f, v in _fields(buf, *r):
        if f == STAT_MD_ID:
            sid = v
        elif f == STAT_STR:
            val = _str(buf, v)
        elif f == STAT_REF:
            val = st_md.get(v)
    return st_md.get(sid), val


def read_xspace(path):
    """({device op name: tf_op}, number of op names seen with two
    different tf_ops, [(name, start_ns, dur_ns)] of the ``repro.*``
    host spans)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    tf_ops, clashes, host = {}, set(), []
    for f, r in _fields(buf, 0, len(buf)):
        if f != XSPACE_PLANES:
            continue
        name, lines, ev_md, st_md = _plane(buf, r)
        if xtrace.DEVICE_PLANE.match(name):
            for ev_name, stats in ev_md.values():
                for sr in stats:
                    k, v = _stat_str(buf, sr, st_md)
                    if k != TF_OP or v is None:
                        continue
                    if tf_ops.setdefault(ev_name, v) != v:
                        clashes.add(ev_name)
        elif name == HOST_PLANE:
            want = {i: n for i, (n, _) in ev_md.items()
                    if n.startswith(HOST_PREFIX)}
            if want:
                host += _host_events(buf, lines, want)
    return tf_ops, len(clashes), sorted(host, key=lambda e: e[1])


def _host_events(buf, lines, want):
    out = []
    for lr in lines:
        t0, events = 0, []
        for f, v in _fields(buf, *lr):
            if f == LINE_TIMESTAMP_NS:
                t0 = v
            elif f == LINE_EVENTS:
                events.append(v)
        for er in events:
            md, off, dur = 0, 0, 0
            for f, v in _fields(buf, *er):
                if f == EVENT_MD_ID:
                    md = v
                elif f == EVENT_OFFSET_PS:
                    off = v
                elif f == EVENT_DURATION_PS:
                    dur = v
            if md in want:
                out.append((want[md], t0 + off // 1000, dur // 1000))
    return out


# -- name stacks ---------------------------------------------------------

def categories(tf_op: str) -> tuple:
    """The scopes an op with name stack ``tf_op`` counts to, with
    ``forward`` split into ``forward`` and ``backward``."""
    parts = tf_op.rsplit(":", 1)[0].split("/")
    bare = [_WRAP.sub("", p) for p in parts]
    back = any(b == FORWARD and p.startswith("transpose(")
               for p, b in zip(parts, bare))
    out = [s for s in SCOPES if s in bare]
    if back:
        out[out.index(FORWARD)] = BACKWARD
    return tuple(out)


class Scoped:
    """Device seconds per scope (summed over devices, as
    ``Trace.op_seconds``) and the program's host spans, of one trace."""

    def __init__(self, trace, tf_ops: dict, clashes: int, host: list):
        self.trace, self.host, self.clashes = trace, host, clashes
        self.n_names = len(tf_ops)
        self.busy = {d: trace.busy_intervals(d) for d in trace.devices}
        # scopes (() where none) -> op name -> device ns
        self.ops = collections.defaultdict(collections.Counter)
        memo = {}     # op event name -> (its scopes, its op name)
        for d in trace.devices:
            for name, _, du in trace.ops[d]:
                hit = memo.get(name)
                if hit is None:
                    hit = memo[name] = (
                        None if xtrace.CONTROL.match(name)
                        else (categories(tf_ops.get(name, "")),
                              xtrace.op_name(name)))
                if hit is not None:
                    self.ops[hit[0]][hit[1]] += du

    @classmethod
    def of(cls, ctx):
        """The run's scoped trace, read once per run (kept in ctx) from
        the directory ``bench/run.py`` traces the cell into."""
        if "scoped" not in ctx:
            t = time.perf_counter()
            tdir = spec.ROOT / ".bench_trace" / ctx["cell"]["name"]
            s = cls(ctx["trace"], *read_xspace(xtrace.xplane_file(str(tdir))))
            ctx["scoped"] = s
            s.note(time.perf_counter() - t)
        return ctx["scoped"]

    def seconds(self, *scopes) -> float:
        """Device seconds of the ops under any of ``scopes``."""
        return sum(sum(ops.values()) for cats, ops in self.ops.items()
                   if any(s in cats for s in scopes)) / 1e9

    def step_spans(self):
        return [h for h in self.host if h[0].startswith(STEP_SPANS)]

    def idle_by_span(self) -> dict:
        """{host span name: idle device seconds}, averaged over devices.
        Each idle gap of the window goes wholly to the span whose own
        time (its interval less the spans nested in it) overlaps most
        of the gap: the host and device clocks differ by about a
        millisecond, so the span at a gap's midpoint can be a
        neighbour."""
        t = self.trace
        spans = list(t.host) + self.host
        own = [(name, _own_time(s, s + du, spans)) for name, s, du in spans]
        tot = collections.Counter()
        for d, busy in self.busy.items():
            edges = [t.t0] + [x for iv in busy for x in iv] + [t.t1]
            for a, b in zip(edges[::2], edges[1::2]):
                if b <= a:
                    continue
                best, most = "outside spans", 0
                for name, ivs in own:
                    ov = sum(max(0, min(b, e) - max(a, s)) for s, e in ivs)
                    if ov > most:
                        best, most = name, ov
                tot[best] += b - a
        k = max(1, len(t.devices))
        return {name: v / k / 1e9 for name, v in tot.items()}

    def skew_ms(self):
        """Per step, the device's train-step program start minus the
        host's ``repro.step.dispatch`` start; the least over steps (ms),
        or None where the two do not pair up."""
        disp = [s for n, s, _ in self.host if n == STEP_SPANS + "dispatch"]
        mods = sorted(s for d in self.trace.modules
                      for n, s, _ in self.trace.modules[d]
                      if TRAIN_MODULE.match(n))
        if not disp or len(disp) != len(mods):
            return None
        return min(m - h for h, m in zip(disp, mods)) / 1e6

    def note(self, read_s):
        """One line on standard error: what the reading found."""
        busy = sum(b - a for ivs in self.busy.values() for a, b in ivs) / 1e9
        print("scopes: " + json.dumps({
            "op_names": self.n_names, "op_names_with_two_tf_ops":
            self.clashes, "read_s": read_s, "busy_s": busy,
            "forward_backward_optimizer_share_of_busy":
            self.seconds(*STEP_PARTS) / busy if busy else None,
            "scopes_s": {"/".join(c) or "unscoped": [
                sum(ops.values()) / 1e9,
                [[n, v / 1e9] for n, v in ops.most_common(3)]]
                for c, ops in sorted(self.ops.items(),
                                     key=lambda kv: -sum(kv[1].values()))},
            "idle_by_span_s": self.idle_by_span(),
            "dispatch_to_device_skew_ms": self.skew_ms()}),
            file=sys.stderr)


def ms_per_step(ctx, *scopes):
    """Device ms per traced step under any of ``scopes``; None where
    the trace has none (a program without the scopes)."""
    secs = Scoped.of(ctx).seconds(*scopes)
    if secs <= 0 or ctx["steps"] < 1:
        return None
    return 1e3 * secs / ctx["steps"]


def _own_time(s, e, spans):
    """[s, e) less the spans strictly nested in it, as intervals."""
    cuts = sorted((cs, cs + cd) for _, cs, cd in spans
                  if s <= cs and cs + cd <= e and (cs, cs + cd) != (s, e))
    out, at = [], s
    for cs, ce in cuts:
        if cs > at:
            out.append((at, cs))
        at = max(at, ce)
    if at < e:
        out.append((at, e))
    return out
