"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<run>/*.xplane.pb``; JAX's
own ``ProfileData`` reads it.  Device planes are named ``/device:TPU:<n>``
and their ``XLA Ops`` line holds one event per operation run on the chip;
the host plane holds the benchmark's ``jax.profiler.TraceAnnotation`` spans
(``bench.plan``, ``bench.step``) beside JAX's own.  All events share one
clock, in nanoseconds.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# host spans shorter than this never label a gap (keeps the search small)
HOST_SPAN_MIN_NS = 20_000


def find(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> dict:
    """{"devices": [[(start_ns, end_ns, name), ...] per chip],
    "host": [(start_ns, end_ns, name), ...]}"""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.start_ns, e.end_ns, e.name)
                               for e in line.events)
            devices.append(sorted(ops))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.start_ns, e.end_ns, e.name)
                            for e in line.events)
    return {"devices": devices, "host": sorted(host)}


def union(events) -> list:
    """Merged [start, end] intervals covered by the events."""
    out = []
    for s, e, _n in sorted(events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def op_family(name: str) -> str:
    """An op's name without its instance number or its HLO text
    (``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``), so the breakdown
    sums alike operations."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[.\-_]\d+$", "", head)


def reduce(data: dict, window_s: float) -> dict:
    """``busy_s``: seconds in which an operation ran, averaged over the
    chips; ``op_s``: device seconds of each operation family, summed over
    the chips; ``device_ops``: the ten
    operation families that took most device time; ``idle_gaps``: the ten
    host spans under which the first chip stood idle longest, by the
    innermost host span covering each gap's middle."""
    devices = [d for d in data["devices"] if d]
    if not devices:
        return {"busy_s": 0.0, "window_s": window_s, "op_s": {},
                "device_ops": [], "idle_gaps": []}
    busy = [sum(e - s for s, e in union(d)) for d in devices]
    fam = defaultdict(float)
    for d in devices:
        for s, e, n in d:
            fam[op_family(n)] += (e - s) / 1e9
    merged = union(devices[0])
    gaps = defaultdict(float)
    host = [h for h in data["host"] if h[1] - h[0] >= HOST_SPAN_MIN_NS]
    hs = np.array([h[0] for h in host], np.int64)
    he = np.array([h[1] for h in host], np.int64)
    for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
        mid = (e0 + s1) // 2
        cover = np.nonzero((hs <= mid) & (he >= mid))[0]
        if len(cover):
            label = host[cover[np.argmin(he[cover] - hs[cover])]][2]
        else:
            label = "no host span"
        gaps[label] += (s1 - e0) / 1e9
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": window_s,
        "op_s": dict(fam),
        "device_ops": top(fam),
        "idle_gaps": top(gaps),
    }
