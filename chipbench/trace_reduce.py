"""From a profiler trace to the numbers the per-layer readers need.

``load`` flattens an ``.xplane.pb`` into plain events; ``reduce_events``
does the arithmetic on those, so a small hand-made table can check it:

* the window: from the start of the first harness step span to the end
  of the last one;
* busy time: the union of the device's operation intervals inside the
  window, averaged over the device planes;
* per program: executions and device time of each jitted program (the
  device's module line), named as the program names its jit;
* per kernel: device time of the operations whose name or metadata holds
  a kernel's name;
* idle gaps, each named by the innermost host span around its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"
LABEL_STATS = ("long_name", "hlo_op", "tf_op", "name", "kernel_details")


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start: float          # ns, one clock for every plane
    dur: float            # ns
    label: str = ""       # name plus metadata, for matching kernels

    @property
    def end(self) -> float:
        return self.start + self.dur


def profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # no per-call python tracing
    opts.host_tracer_level = 2        # keep annotations and jax's spans
    return opts


def load(path: str) -> list:
    """Every event of an ``.xplane.pb`` file, with metadata folded into
    ``label`` for the device planes."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        dev = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            for e in line.events:
                label = e.name
                if dev:
                    extra = [str(v) for k, v in e.stats
                             if k in LABEL_STATS and isinstance(v, str)]
                    label = " ".join([e.name] + extra)
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 label))
    return out


def _union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def op_kind(name: str) -> str:
    """A device op's HLO text -> its instruction name without numbering
    (``%copy.53 = ...`` -> ``copy``); a custom call keeps its result type,
    which tells the kernels apart (``decode_greedy bf16[64,36,64]``)."""
    lhs, _, rhs = name.partition(" = ")
    kind = re.sub(r"\.\d+", "", lhs).lstrip("%")
    if "custom-call(" in rhs:
        kind += " " + rhs.split("{", 1)[0]
    return kind


def _program(name: str) -> str:
    """``jit_decode_greedy(123)`` -> ``decode_greedy``."""
    name = re.sub(r"\(.*$", "", name)
    return re.sub(r"^jit_", "", name)


@dataclasses.dataclass
class Reduced:
    t0: float
    t1: float
    busy_s: float
    ops: list             # device op events inside the window
    modules: list         # device program executions inside the window
    host: list            # host events inside the window
    gaps: list            # (start, end) idle intervals of the first device
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def programs(self) -> dict:
        """``{program: (executions, device seconds)}`` over all devices,
        executions averaged per device."""
        acc = {}
        for e in self.modules:
            n, s = acc.get(_program(e.name), (0, 0.0))
            acc[_program(e.name)] = (n + 1, s + e.dur * 1e-9)
        return {k: (n / self.n_devices, s / self.n_devices)
                for k, (n, s) in acc.items()}

    def kernel(self, pattern: str) -> tuple:
        """``(events, device seconds)`` of the operations whose label
        matches ``pattern`` (a regular expression), per device."""
        rx = re.compile(pattern)
        hit = [e for e in self.ops if rx.search(e.label)]
        return (len(hit) / self.n_devices,
                sum(e.dur for e in hit) * 1e-9 / self.n_devices)

    def breakdown(self, top: int = 10) -> dict:
        by_op = {}
        for e in self.ops:
            k = op_kind(e.name)
            by_op[k] = by_op.get(k, 0.0) + e.dur * 1e-9
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, s / self.n_devices] for n, s in ops],
                "idle_gaps": [[self.host_at((s + e) / 2), (e - s) * 1e-9]
                              for s, e in gaps]}

    def host_at(self, t: float) -> str:
        """The innermost host span around time ``t``."""
        best = None
        for e in self.host:
            if e.start <= t <= e.end and (best is None or e.dur < best.dur):
                best = e
        return best.name if best is not None else "no host span"


def reduce_events(events: list, span: str) -> Reduced:
    steps = [e for e in events if e.name == span
             and not e.plane.startswith(DEVICE_PREFIX)]
    if not steps:
        raise ValueError(f"no {span!r} spans in the trace")
    t0 = min(e.start for e in steps)
    t1 = max(e.end for e in steps)

    def inside(e):
        return e.end > t0 and e.start < t1

    dev_planes = sorted({e.plane for e in events
                         if e.plane.startswith(DEVICE_PREFIX)
                         and e.line == OPS_LINE})
    if not dev_planes:
        raise ValueError(f"no device plane with a {OPS_LINE!r} line")
    ops = [e for e in events if e.line == OPS_LINE
           and e.plane in dev_planes and inside(e)]
    modules = [e for e in events if e.line == MODULES_LINE
               and e.plane in dev_planes and inside(e)]
    host = [e for e in events if not e.plane.startswith(DEVICE_PREFIX)
            and inside(e)]
    busy, gaps = 0.0, []
    for i, plane in enumerate(dev_planes):
        merged = _union((max(e.start, t0), min(e.end, t1))
                        for e in ops if e.plane == plane)
        busy += sum(e - s for s, e in merged)
        if i == 0:
            edges = [t0] + [x for iv in merged for x in iv] + [t1]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    return Reduced(t0, t1, busy * 1e-9 / len(dev_planes), ops, modules,
                   host, gaps, len(dev_planes))


def reduce_dir(directory: str, span: str) -> Reduced:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {directory}, found "
                         f"{len(paths)}")
    return reduce_events(load(paths[0]), span)


@dataclasses.dataclass
class Context:
    """What a per-layer reader gets: the reduced trace, the harness's
    records of the traced steps, the model's sizes and plan, the peaks,
    and the configuration's work module (``work.py`` unless the
    configuration names another), which counts the model's operations
    and bytes: ``model_flops``, ``qlinear_roofline_s`` and
    ``paged_decode_bytes``."""
    trace: Reduced
    steps: list
    cfg: object
    bits: dict | None
    peaks: dict
    work: object
