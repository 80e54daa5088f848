"""Device time of the dycore step by layer and by kernel, read from the
step's own scopes, and its idle gaps labelled by the host's spans.

The step runs each program under a named scope (``c_sw_riem``, ``d_sw``,
``tracer_2d``, ``vertical_remap``), each halo exchange under
``halo_exchange``, and each stencil node under its label (``al_x#3``); the
compiled step carries them as each instruction's ``op_name`` metadata.
``scope_map`` reads them from the compiled step's HLO text into
{instruction name: [layer, node label]}; a fusion has its root's op_name.
The trace names each device op by its instruction, so ``reduce`` can give,
per physics step:

* each layer's busy time inside the step modules: the union of the
  intervals of its leaf ops (an op enclosing others, such as a ``while``
  loop, is not itself work), and ``other``, the busy time no scoped op
  covers (the loops' bookkeeping and the copies the compiler put in);
* ``overlap``: the time that two layers' ops cover at once (the layers'
  times sum to the scoped busy time plus it);
* each kernel's summed device time, by stencil node, and the costliest
  unscoped instructions;
* each idle gap inside the traced window, labelled by the innermost host
  span (``bench.*`` or ``repro.*``) in progress at its start: the device
  and host timelines of one profile share a clock, up to a skew that
  ``step_spans`` measures.

``read_events`` is ``tracing.read_events`` with the program's ``repro.*``
host spans kept beside the harness's ``bench.*``.  ``trace_step.py`` runs
a cell's step under the profiler and writes all of this.
"""

from __future__ import annotations

import glob
import os
import re

import tracing

LAYERS = ("c_sw_riem", "d_sw", "tracer_2d", "vertical_remap",
          "halo_exchange")
OTHER = "other"
#: the step's own jit, the root of every op_name inside it
STEP = "jit(_inner)"
HOST_PREFIXES = ("bench.", "repro.")

_INSTRUCTION = re.compile(
    r'^\s+(?:ROOT )?%(\S+) = .*?metadata=\{op_name="((?:[^"\\]|\\.)*)"', re.M)
_LABEL = re.compile(r"\S+#f?\d+")


def _parts(op_name: str) -> list[str]:
    """The op_name's path with each transform wrapper (``vmap(...)``,
    ``jit(...)``) removed."""
    out = []
    for p in op_name.split("/"):
        while (m := re.fullmatch(r"\w+\((.*)\)", p)):
            p = m.group(1)
        out.append(p)
    return out


def scope_map(hlo: str) -> dict[str, list[str]]:
    """{instruction name: [layer, node label or ""]} for every instruction
    of the compiled step whose op_name lies under one layer scope."""
    out = {}
    for name, op_name in _INSTRUCTION.findall(hlo):
        if not op_name.startswith(STEP):
            continue
        path = _parts(op_name)
        layers = [p for p in path if p in LAYERS]
        if len(layers) != 1:
            continue
        after = path[path.index(layers[0]) + 1:]
        label = next((p for p in after if _LABEL.fullmatch(p)), "")
        out[name] = [layers[0], label]
    return out


def read_events(trace_dir: str) -> dict:
    """``tracing.read_events`` with the ``repro.*`` host spans added."""
    from jax.profiler import ProfileData

    events = tracing.read_events(trace_dir)
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        events["host"].append(
                            [e.name, int(e.start_ns), int(e.duration_ns)])
    events["host"].sort(key=lambda h: (h[1], -h[2]))
    return events


def innermost(spans, t: int) -> str:
    """Name of the latest-started host span in progress at ``t``."""
    live = [s for s in spans
            if s[0].startswith(HOST_PREFIXES) and s[1] <= t < s[1] + s[2]]
    return max(live, key=lambda s: (s[1], -s[2]))[0] if live else "none"


def step_modules(lines: dict) -> list[list]:
    return [m for m in lines.get(tracing.MODULE_LINE, [])
            if not m[0].startswith(tracing.PROBE_PREFIX)]


def reduce(events: dict, scopes: dict, steps: int) -> dict:
    """Per physics step, on the first device that ran the step: each
    layer's busy ms, ``other`` and ``overlap``, the busy and window ms, the
    costliest kernels by stencil node, and the idle gaps inside the window
    labelled by host span."""
    for _, lines in sorted(events["devices"].items()):
        mods = step_modules(lines)
        if mods and lines.get(tracing.OP_LINE):
            break
    else:
        raise ValueError("the trace holds no step module and op events")
    lo = min(m[1] for m in mods)
    hi = max(m[1] + m[2] for m in mods)
    ops = tracing.leaves(o for o in lines[tracing.OP_LINE]
                         if o[1] < hi and o[1] + o[2] > lo)
    busy = tracing.union(tracing.clip(
        [(o[1], o[1] + o[2]) for o in ops], lo, hi))
    per_layer, kernels = {}, {}
    for layer in LAYERS:
        per_layer[layer] = tracing.union(tracing.clip(
            [(o[1], o[1] + o[2]) for o in ops
             if scopes.get(o[0], [None])[0] == layer], lo, hi))
    scoped = tracing.union(iv for ivs in per_layer.values() for iv in ivs)
    unscoped: dict[str, list] = {}
    for name, _, dur, _ in ops:
        layer, label = scopes.get(name, [OTHER, ""])
        key = f"{layer}/{label.partition('#')[0]}" if label else layer
        kernels[key] = kernels.get(key, 0.0) + dur
        if layer == OTHER:
            u = unscoped.setdefault(name, [name, 0.0, 0])
            u[1] += dur * 1e-6 / steps
            u[2] += 1
    ms = 1e-6 / steps
    gaps = [[innermost(events["host"], e0), (s1 - e0) * 1e-9]
            for (_, e0), (s1, _) in zip(busy, busy[1:])]
    return {
        "steps": steps,
        "window_ms": (hi - lo) * ms,
        "busy_ms": tracing.length(busy) * ms,
        "in_step_ms": {
            **{k: tracing.length(v) * ms for k, v in per_layer.items()},
            OTHER: tracing.length(tracing.subtract(busy, scoped)) * ms},
        "overlap_ms": (sum(tracing.length(v) for v in per_layer.values())
                       - tracing.length(scoped)) * ms,
        "kernels_ms": sorted(([k, v * ms] for k, v in kernels.items()),
                             key=lambda x: -x[1])[:20],
        # [instruction, ms per step, calls in all steps]
        "other_ops": sorted(unscoped.values(), key=lambda x: -x[1])[:10],
        "idle_gaps": sorted(gaps, key=lambda x: -x[1])[:10],
        "idle_by_span_ms": _by_span(gaps, steps),
    }


def _by_span(gaps, steps: int) -> dict[str, float]:
    out: dict[str, float] = {}
    for span, s in gaps:
        out[span] = out.get(span, 0.0) + s * 1e3 / steps
    return out


def step_spans(events: dict) -> list[dict]:
    """Each step module of the first device beside the ``bench.step`` host
    span that ran it (the n-th of each): the share of the module inside
    the span, and how many ms the module's start and end lie before the
    span's.  A module cannot start before the span that dispatched it, so
    a start lead above zero is the skew between the two timelines."""
    spans = sorted((h for h in events["host"] if h[0] == "bench.step"),
                   key=lambda h: h[1])
    lines = next(iter(sorted(events["devices"].items())))[1]
    out = []
    for m, s in zip(sorted(step_modules(lines), key=lambda m: m[1]), spans):
        inside = min(m[1] + m[2], s[1] + s[2]) - max(m[1], s[1])
        out.append({"inside": max(inside, 0) / m[2],
                    "start_lead_ms": (s[1] - m[1]) * 1e-6,
                    "end_lead_ms": (s[1] + s[2] - m[1] - m[2]) * 1e-6})
    return out
