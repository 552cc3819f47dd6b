"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers, with nothing but ``jax.profiler.ProfileData``.

* Device planes are ``/device:TPU:<n>``; their operations are the events
  of the ``XLA Ops`` line.
* Busy time is the union of those operations' intervals inside the
  benchmark's window (the host span ``bench.window``), per device; the
  idle share is one minus busy over the window.  Both are averaged over
  the devices.
* An operation is a Pallas kernel when the trace records it as a custom
  call into a Mosaic kernel (``is_pallas``); every other operation is XLA
  glue.
* Each idle gap of the first device is labelled by what the host was
  doing: the benchmark's span (``bench.step``, ``bench.wait``,
  ``bench.admit``) and the traced call on the same thread (a JAX host
  event such as ``np.asarray(jax.Array)``) that cover most of it
  (``gap_label``).
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.step", "bench.wait", "bench.admit")
TOP = 10
MIN_GAP_NS = 1000

Interval = Tuple[int, int]


def is_pallas(name: str) -> bool:
    """A Pallas kernel: the op's HLO text calls ``tpu_custom_call``."""
    return 'custom_call_target="tpu_custom_call"' in name


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def gap_label(g: Interval, host: Dict[str, List[Interval]]) -> str:
    """The benchmark span that covers most of an idle gap, then the traced
    host call on the benchmark's thread that covers most of it, or
    ``python`` where untraced host code (NumPy, the server's own Python)
    covers most of it."""
    def most(names):
        best, label = 0, None
        for n in names:
            ov = sum(overlap(g, iv) for iv in host.get(n, ()))
            if ov > best:
                best, label = ov, n
        return best, label
    _, span = most(HOST_SPANS)
    ov, call = most([n for n in host if not n.startswith("bench.")])
    if ov * 2 < g[1] - g[0]:
        call = "python"
    return f"{span or 'no bench span'} > {call}"


def _events(pd):
    """(device planes {name: [(start, end, op HLO text)]}, the events of
    the benchmark's host thread {name: [(start, end)]})."""
    devices: Dict[str, list] = {}
    host: Dict[str, List[Interval]] = defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    evs.append((s, s + int(e.duration_ns), e.name))
            devices[plane.name] = evs
        else:
            # the benchmark's thread is the one that holds its spans
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events]
                if not any(n == WINDOW_SPAN for n, _, _ in evs):
                    continue
                for n, s, d in evs:
                    host[n].append((s, s + d))
    return devices, host


def summarize(path: str, devices: int) -> Optional[Dict]:
    """Device numbers of the traced window, from the trace at ``path``;
    None where the trace holds no TPU (a run on the CPU)."""
    from jax.profiler import ProfileData
    return reduce(*_events(ProfileData.from_file(path)), devices=devices)


def reduce(dev_events: Dict[str, list], host: Dict[str, List[Interval]],
           devices: int) -> Optional[Dict]:
    """Busy, Pallas and other op time per device (averaged over the first
    ``devices``), the top ops and the longest labelled idle gaps, inside
    the longest ``bench.window`` span."""
    if not host.get(WINDOW_SPAN):
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = max(host[WINDOW_SPAN], key=lambda iv: iv[1] - iv[0])
    names = sorted(dev_events, key=_device_index)[:devices]
    if not names:
        return None
    if len(names) < devices:
        raise ValueError(f"the trace holds {len(names)} device planes, "
                         f"the run used {devices}")
    busy_ns, pallas_ns, other_ns = [], 0, 0
    per_op: Dict[str, int] = defaultdict(int)
    first_busy: List[Interval] = []
    for i, n in enumerate(names):
        evs = [(max(s, lo), min(e, hi), name)
               for s, e, name in dev_events[n] if e > lo and s < hi]
        b = union([(s, e) for s, e, _ in evs])
        busy_ns.append(sum(e - s for s, e in b))
        if i == 0:
            first_busy = b
        for s, e, name in evs:
            if is_pallas(name):
                pallas_ns += e - s
            else:
                other_ns += e - s
            per_op[op_label(name)] += e - s
    window_ns = hi - lo
    # ops that abut leave gaps of a few nanoseconds; they are not idle time
    idle = [g for g in gaps(first_busy, lo, hi) if g[1] - g[0] >= MIN_GAP_NS]
    labelled = []
    for g in sorted(idle, key=lambda g: g[0] - g[1])[:TOP]:
        labelled.append([gap_label(g, host), (g[1] - g[0]) / 1e9])
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    k = len(names)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns) / k / 1e9,
        "pallas_s": pallas_ns / k / 1e9,
        "other_s": other_ns / k / 1e9,
        "breakdown": {
            "device_ops": [[n, v / k / 1e9] for n, v in top_ops],
            "idle_gaps": labelled,
        },
    }


def _device_index(plane_name: str) -> int:
    tail = plane_name[len(DEVICE_PREFIX):]
    return int(tail) if tail.isdigit() else 1 << 30


def op_label(name: str) -> str:
    """An operation's HLO name and result type, without the layout: the
    trace names an op by its whole HLO text,
    ``%conv_fused.4 = f32[64,256,338]{2,1,0:T(8,128)} custom-call(...)``."""
    head, _, rest = name.partition(" = ")
    shape = rest.split(" ", 1)[0].split("{", 1)[0]
    return f"{head.lstrip('%')} {shape}".strip()
