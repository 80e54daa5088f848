"""From a profiler trace to the numbers the per-layer metrics read.

``read_events`` keeps, of an ``.xplane.pb``, each device plane's module
events and op events (the HLO instruction's name, start, duration, and
whether it is a Mosaic kernel: a custom call to ``tpu_custom_call``) and
the harness's own ``bench.*`` host annotations.  ``reduce`` works on that
and on the device timeline alone:

* the traced window runs from the start of the first whole-step module
  to the end of the last (every module that is not a probe's is the
  step's: nothing else runs while the steps are traced);
* busy time is the union of the leaf op intervals inside the window (an op
  that encloses others, such as a ``while`` loop, is not itself work);
* Mosaic time is the union of the Mosaic kernels' intervals in it, and
  the XLA time the busy time that no Mosaic kernel covers;
* a probe's device time is the summed duration of the modules named
  ``jit_bench_probe_<layer>``.
"""

from __future__ import annotations

import glob
import os
import re

PROBE_PREFIX = "jit_bench_probe_"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


MOSAIC = 'custom_call_target="tpu_custom_call"'


def op_event(e) -> list:
    """[instruction name, start ns, duration ns, "mosaic" or ""]; the
    trace names an op by its whole HLO instruction text."""
    text = e.name
    name = text.split(" = ", 1)[0].lstrip("%")
    return [name, int(e.start_ns), int(e.duration_ns),
            "mosaic" if MOSAIC in text else ""]


def read_events(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[0])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    lines[line.name] = [
                        [e.name, int(e.start_ns), int(e.duration_ns), ""]
                        for e in line.events]
                elif line.name == OP_LINE:
                    lines[line.name] = [op_event(e) for e in line.events]
            if lines:
                out["devices"][plane.name] = lines
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["host"].append(
                            [e.name, int(e.start_ns), int(e.duration_ns)])
    return out


def union(intervals) -> list[tuple[int, int]]:
    """Merged (start, end) intervals, sorted."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[int, int]]:
    """Parts of the merged intervals ``a`` that no interval of merged
    ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def leaves(ops) -> list:
    """The ops that enclose no other op."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= o[1] + o[2]
            or nxt[1] + nxt[2] > o[1] + o[2]]


def reduce_device(lines: dict, steps: int) -> dict | None:
    modules = lines.get(MODULE_LINE, [])
    ops = lines.get(OP_LINE, [])
    step_mods = [m for m in modules if not m[0].startswith(PROBE_PREFIX)]
    if not step_mods or not ops:
        return None
    lo = min(m[1] for m in step_mods)
    hi = max(m[1] + m[2] for m in step_mods)
    in_win = leaves(op for op in ops if op[1] < hi and op[1] + op[2] > lo)
    busy = union(clip([(o[1], o[1] + o[2]) for o in in_win], lo, hi))
    mosaic = union(clip([(o[1], o[1] + o[2]) for o in in_win
                         if o[3] == "mosaic"], lo, hi))
    probes: dict[str, dict] = {}
    for name, start, dur, _ in modules:
        if name.startswith(PROBE_PREFIX):
            # the module name may carry a suffix such as "(123)" or ".1"
            layer = re.match(r"\w*", name[len(PROBE_PREFIX):]).group(0)
            p = probes.setdefault(layer, {"calls": 0, "device_s": 0.0})
            p["calls"] += 1
            p["device_s"] += dur * 1e-9
    by_op: dict[str, float] = {}
    for name, s, d, _ in in_win:
        by_op[name] = by_op.get(name, 0.0) + d * 1e-9
    gaps = []
    mods = [(m[1], m[1] + m[2]) for m in step_mods]
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        where = next((f"inside step {i + 1}"
                      for i, (a, b) in enumerate(sorted(mods))
                      if a <= e0 and s1 <= b), "between steps")
        gaps.append([where, (s1 - e0) * 1e-9])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": length(busy) * 1e-9,
        "mosaic_s": length(mosaic) * 1e-9,
        "xla_s": length(subtract(busy, mosaic)) * 1e-9,
        "step_modules": len(step_mods),
        "steps": steps,
        "probes": probes,
        "breakdown": {
            "device_ops": sorted(([n, t] for n, t in by_op.items()),
                                 key=lambda x: -x[1])[:10],
            "idle_gaps": sorted(gaps, key=lambda x: -x[1])[:10],
        },
    }


def reduce(events: dict, steps: int) -> dict:
    """Per-device reductions averaged over the devices that ran the step
    (probes and breakdown from the first of them)."""
    per = [r for _, lines in sorted(events["devices"].items())
           if (r := reduce_device(lines, steps)) is not None]
    if not per:
        raise ValueError("the trace holds no device module and op events")
    out = dict(per[0])
    for key in ("window_s", "busy_s", "mosaic_s", "xla_s"):
        out[key] = sum(r[key] for r in per) / len(per)
    return out
