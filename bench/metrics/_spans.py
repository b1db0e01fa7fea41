"""The program's own spans in the traced run, and the chip's idle time put
down to the layer whose span covered it.

The program names its spans ``conserve.<layer>.<what>``; each turn of its
engine loop is one ``conserve.runtime.iteration`` root on the engine
thread's line of the profiler's host plane, with the planned ``tokens`` as
a stat (0: the turn ran no plan).  Idle is the traced window minus the
union of chip 0's operation intervals.  Each idle instant goes to the
innermost program span over it on that line, split exactly at every span
and operation boundary; every other host event (the Python tracer's
frames, JAX's own, the benchmark's ``bench.*``) is passed over.  The
span's name picks its share (``SHARES``); idle under a ``kv.*`` span or
under none is unattributed.  A share is 100 x idle seconds over the
window's host seconds, the denominator of ``device_idle_pct``.  A trace
without program spans reads None.

The window's host seconds are placed on the trace's clock to end where the
engine line's last program span ends: the benchmark stops the profiler
between loop turns, right after an engine step, and every operation of a
step ends before the step does.  Whatever of those seconds passed before
the profiler began recording then lies at the window's start, under no
span.  (The latest event of the whole trace would not do: the Python
tracer closes the frames still open at the stop only when the stop has
finished writing, up to a second later.)
"""
from __future__ import annotations

import functools
import statistics

from bench import trace as tracing

PREFIX = "conserve."
ROOT = PREFIX + "runtime.iteration"
CHIP0 = "/device:TPU:0"
# share -> the spans (after the prefix) whose idle instants it takes
SHARES = {
    "sched": ("sched.plan", "sched.commit", "engine.events"),
    "build": ("engine.build",),
    "put": ("engine.put",),
    "launch": ("engine.dispatch", "engine.sample"),
    "runtime": ("runtime.drain", "runtime.pump", "runtime.publish",
                "runtime.iteration", "engine.step"),
    "no_work": ("runtime.idle", "runtime.backoff"),
}
KV = ("kv.swap_out", "kv.cow", "kv.restore", "kv.checkpoint")
SHARE_OF = {PREFIX + s: share for share, names in SHARES.items() for s in names}


def load(path: str) -> dict:
    """{"ops": chip 0's (start_ns, end_ns, name), "spans": the engine
    line's program spans (``engine_line``)}"""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, lines = [], []
    for plane in pd.planes:
        if plane.name == CHIP0:
            for line in plane.lines:
                if line.name == tracing.OPS_LINE:
                    ops.extend((e.start_ns, e.end_ns, e.name)
                               for e in line.events)
        elif plane.name == tracing.HOST_PLANE:
            for line in plane.lines:
                lines.append([(e.start_ns, e.end_ns, e.name,
                               {k: v for k, v in e.stats})
                              for e in line.events
                              if e.name.startswith(PREFIX)])
    return {"ops": ops, "spans": engine_line(lines)}


def engine_line(lines) -> list:
    """The program spans ``(start, end, name, stats)`` of the host line
    that holds the most loop turns (the engine thread), keeping only names
    under the program's prefix: every other event is passed over."""
    lines = [[x for x in line if x[2].startswith(PREFIX)] for line in lines]
    best = max(lines, key=lambda sp: sum(1 for x in sp if x[2] == ROOT),
               default=[])
    return best if any(x[2] == ROOT for x in best) else []


def pieces(spans) -> list:
    """Non-overlapping ``(start, end, label)`` pieces, each labelled by the
    innermost span over it: its share, ``"kv"``, or ``"no_work"`` inside a
    root whose plan was empty.  Spans of one thread nest; a child that
    overruns its parent is cut at the parent's end."""
    out, stack, cur = [], [], None  # stack: (end, label, in_empty_root)
    for s, e, name, stats in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, label, _empty = stack.pop()
            out.append((cur, end, label))
            cur = end
        if stack:
            out.append((cur, s, stack[-1][1]))
            e = min(e, stack[-1][0])
        empty = (name == ROOT and not stats.get("tokens")) or bool(
            stack and stack[-1][2])
        label = "no_work" if empty else SHARE_OF.get(name, "kv")
        stack.append((e, label, empty))
        cur = s
    while stack:
        end, label, _empty = stack.pop()
        out.append((cur, end, label))
        cur = end
    return [p for p in out if p[1] > p[0]]


def partition(ops, spans, w0: float, w1: float) -> dict:
    """Idle nanoseconds of [w0, w1] by label, and ``"idle"`` their total."""
    busy = [[max(s, w0), min(e, w1)] for s, e in tracing.union(ops)
            if e > w0 and s < w1]
    idle, t = [], w0
    for s, e in busy:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if t < w1:
        idle.append((t, w1))
    out = {"idle": sum(e - s for s, e in idle)}
    ps = pieces(spans)
    i = 0
    for s, e in idle:
        while i < len(ps) and ps[i][1] <= s:
            i += 1
        j = i
        while j < len(ps) and ps[j][0] < e:
            a, b, label = ps[j]
            cut = min(b, e) - max(a, s)
            if cut > 0:
                out[label] = out.get(label, 0.0) + cut
            j += 1
    return out


def shares(data: dict, window_s: float):
    """Each share of ``SHARES`` and ``"unattributed"`` in % of the window,
    ``"idle"`` their total, and ``"iteration_ms"``; None without program
    spans or chip operations."""
    if not data["spans"] or not data["ops"]:
        return None
    w1 = max(e for _s, e, _n, _st in data["spans"])
    w0 = w1 - window_s * 1e9
    part = partition(data["ops"], data["spans"], w0, w1)
    out = {k: 100.0 * part.get(k, 0.0) / 1e9 / window_s
           for k in ("idle", *SHARES)}
    out["unattributed"] = out["idle"] - sum(out[k] for k in SHARES)
    ran = [e - s for s, e, n, stats in data["spans"]
           if n == ROOT and stats.get("tokens") and s >= w0 and e <= w1]
    out["iteration_ms"] = statistics.median(ran) / 1e6 if ran else None
    return out


@functools.lru_cache(maxsize=1)
def _read(path: str, window_s: float):
    return shares(load(path), window_s)


def read(r, key: str):
    """``key`` of the traced run's partition (a share in % of the window,
    or ``iteration_ms``); None without a trace, a chip, or program
    spans."""
    if r.trace is None or r.trace["window_s"] <= 0:
        return None
    from bench.run import TRACE_DIR

    try:
        path = tracing.find(TRACE_DIR)
    except FileNotFoundError:
        return None
    got = _read(path, float(r.trace["window_s"]))
    return None if got is None else got[key]
