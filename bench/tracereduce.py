"""From a ``jax.profiler`` trace to device intervals, and from those to the
traced run's ``busy_s``, ``window_s`` and ``breakdown``.

A trace is first reduced to plain data (:func:`plain`): planes, their
lines, and events as ``[name, start_ns, duration_ns]``.  Device planes are
those named ``/device:TPU:<n>``; on each, the ``XLA Ops`` line gives the
intervals in which an operation ran (busy time) and the ``XLA Modules``
line the compiled programs (``jit_sc_seeker_seg`` and the like).  One
TraceAnnotation named :data:`MARKER`, opened at a known
``time.monotonic()``, maps the trace's clock onto the server's spans.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from pathlib import Path

MARKER = "bench_clock_marker"
PEAKS = Path(__file__).resolve().parent / "peaks.json"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_ID_SUFFIX = re.compile(r"\(\d+\)$")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind missing from the table is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def plain(profile_data) -> dict:
    """``jax.profiler.ProfileData`` -> plain lists (JSON-serialisable)."""
    return {"planes": [
        {"name": pl.name,
         "lines": [{"name": ln.name,
                    "events": [[ev.name, float(ev.start_ns),
                                float(ev.duration_ns)] for ev in ln.events]}
                   for ln in pl.lines]}
        for pl in profile_data.planes]}


def load_plain(path) -> dict:
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def reduce_dir(log_dir, marker_mono: float, window) -> dict | None:
    """Read the newest ``*.xplane.pb`` under ``log_dir`` and reduce it."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        return None
    return reduce_plain(plain(ProfileData.from_file(files[-1])),
                        marker_mono, window)


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce_plain(data: dict, marker_mono: float, window) -> dict | None:
    """Device op and program intervals on the ``time.monotonic()`` clock,
    clipped to ``window``, with the busy time averaged over the chips."""
    marker = [ev[1] for pl in data["planes"] for ln in pl["lines"]
              for ev in ln["events"] if ev[0] == MARKER]
    if not marker:
        return None
    offset = marker[0] * 1e-9 - marker_mono
    w0, w1 = window
    devices = []
    for pl in data["planes"]:
        if not _DEVICE.match(pl["name"]):
            continue
        lines = {ln["name"]: ln["events"] for ln in pl["lines"]}
        ops = lines.get(OPS_LINE, [])
        mods = lines.get(MODULES_LINE, [])

        def clip(events):
            out = []
            for name, s, d in events:
                a = s * 1e-9 - offset
                b = a + d * 1e-9
                a, b = max(a, w0), min(b, w1)
                if b > a:
                    out.append((name, a, b))
            return out

        ops, mods = clip(ops), clip(mods)
        busy = _union([(a, b) for _, a, b in ops])
        devices.append({"name": pl["name"], "ops": ops, "modules": mods,
                        "busy": busy,
                        "busy_s": sum(b - a for a, b in busy)})
    if not devices:
        return None
    return {"window": (w0, w1), "window_s": w1 - w0,
            "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
            "devices": devices}


def program_name(name: str) -> str:
    return _ID_SUFFIX.sub("", name)


def program_seconds(prof: dict) -> dict:
    """Device seconds per compiled program, summed over the chips."""
    out: dict = {}
    for d in prof["devices"]:
        for name, a, b in d["modules"]:
            n = program_name(name)
            out[n] = out.get(n, 0.0) + (b - a)
    return out


def _span_paths(spans):
    """(path, t0, t1, depth) for every span of the batch trees."""
    out = []

    def walk(s, path, depth):
        p = f"{path}/{s.name}" if path else s.name
        if s.t1 is not None:
            out.append((p, s.t0, s.t1, depth))
        for c in s.children:
            walk(c, p, depth + 1)

    for s in spans:
        walk(s, "", 0)
    return out


def idle_by_host_span(prof: dict, spans) -> dict:
    """Seconds the first chip sat idle in the traced window, keyed by the
    innermost dispatcher span open at the middle of each idle gap
    (``idle`` where no batch was being served)."""
    dev = prof["devices"][0]
    w0, w1 = prof["window"]
    gaps, t = [], w0
    for a, b in dev["busy"]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w1:
        gaps.append((t, w1))
    paths = sorted(_span_paths(spans), key=lambda p: p[1])
    starts = [p[1] for p in paths]
    out: dict = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        best, depth = "idle", -1
        # one dispatcher thread serves batches one after another, so only
        # the spans since the last batch root that began before ``mid``
        # can be open at ``mid``
        for p in reversed(paths[:bisect.bisect_right(starts, mid)]):
            if p[1] <= mid < p[2] and p[3] > depth:
                best, depth = p[0], p[3]
            if p[3] == 0:
                break
        out[best] = out.get(best, 0.0) + (b - a)
    return out


def breakdown(prof: dict, spans) -> dict:
    top = sorted(program_seconds(prof).items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(idle_by_host_span(prof, spans).items(),
                  key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[n, s] for n, s in idle]}


def requests_in_window(ctx) -> list:
    """The traced window's requests: those whose batch started in it."""
    if ctx.device is None:
        return []
    w0, w1 = ctx.device["window"]
    return [r for r in ctx.requests
            if r.batch_t0 is not None and w0 <= r.batch_t0 < w1]
