"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read: device busy time (the union of the intervals in which an operation
ran), idle gaps labelled by the host annotation open at the time, and the
device time of named kernels and jitted programs.

The trace is read with `jax.profiler.ProfileData` alone.  On a TPU each
chip is a plane `/device:TPU:<n>`; its line `XLA Ops` holds one event per
operation run, named by the HLO instruction's text, and `XLA Modules` one
per jitted program run, named `jit_<function>(<fingerprint>)`.  The host's
annotations (`jax.profiler.TraceAnnotation`) are events of `/host:CPU`.
All times here are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Events:
    """Named intervals, in arrays: name[i] ran from start[i] to end[i]."""
    name: list
    start: np.ndarray
    end: np.ndarray

    @classmethod
    def of(cls, rows) -> "Events":
        rows = sorted(rows, key=lambda r: r[1])
        return cls([r[0] for r in rows],
                   np.array([r[1] for r in rows], np.float64),
                   np.array([r[2] for r in rows], np.float64))

    def matching(self, pattern: str) -> "Events":
        rx = re.compile(pattern)
        keep = [i for i, n in enumerate(self.name) if rx.match(n)]
        return Events([self.name[i] for i in keep], self.start[keep],
                      self.end[keep])

    def within(self, lo: float, hi: float) -> "Events":
        keep = np.nonzero((self.end > lo) & (self.start < hi))[0]
        return Events([self.name[i] for i in keep], self.start[keep],
                      self.end[keep])

    def total_s(self) -> float:
        return float((self.end - self.start).sum()) * 1e-9

    def __len__(self) -> int:
        return len(self.name)


INSTRUCTION = re.compile(r"^%?([^\s=]+)")


def instruction(op: str) -> str:
    """The HLO instruction's name of an `XLA Ops` event, which names the
    event by the instruction's text: `%segment_mean.5 = f32[..] ...`."""
    m = INSTRUCTION.match(op)
    return m.group(1) if m else op


def op_pattern(kernel: str) -> str:
    """An HLO instruction of a kernel: `segment_mean`, `segment_mean.3`."""
    return rf"^{re.escape(kernel)}(\.\d+)?$"


def module_pattern(fn: str) -> str:
    """A jitted program's run: `jit_sgd_step`, `jit_sgd_step(12)`."""
    return rf"^jit_{re.escape(fn)}(\(\d+\))?$"


def merge(start: np.ndarray, end: np.ndarray, lo: float, hi: float
          ) -> tuple[np.ndarray, np.ndarray]:
    """The union of the intervals, clipped to [lo, hi], as the starts and
    ends of disjoint sorted intervals."""
    s = np.clip(np.asarray(start, np.float64), lo, hi)
    e = np.clip(np.asarray(end, np.float64), lo, hi)
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    keep = e > s
    s, e = s[keep], e[keep]
    if not len(s):
        return s, e
    reach = np.maximum.accumulate(e)
    first = np.ones(len(s), bool)
    first[1:] = s[1:] > reach[:-1]
    idx = np.nonzero(first)[0]
    last = np.r_[idx[1:] - 1, len(s) - 1]
    return s[idx], reach[last]


def gaps(starts: np.ndarray, ends: np.ndarray, lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """The complement of disjoint sorted intervals within [lo, hi]."""
    edges_a = np.r_[lo, ends]
    edges_b = np.r_[starts, hi]
    keep = edges_b > edges_a
    return list(zip(edges_a[keep].tolist(), edges_b[keep].tolist()))


@dataclasses.dataclass
class DeviceTrace:
    """One traced window: per chip its ops and modules, and the host's
    annotations.  `lo`/`hi` bound the window: the first annotation's start
    and the last one's end."""
    ops: dict            # chip -> Events
    modules: dict        # chip -> Events
    annotations: Events
    lo: float
    hi: float

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        """Union of op intervals in the window, averaged over chips."""
        per = []
        for ev in self.ops.values():
            a, b = merge(ev.start, ev.end, self.lo, self.hi)
            per.append(float((b - a).sum()) * 1e-9)
        return float(np.mean(per)) if per else 0.0

    def idle_gaps(self, top: int = 10) -> list[list]:
        """The longest device-idle gaps of chip 0's window, each with the
        host annotation open at its middle ('none' when none was)."""
        if not self.ops:
            return []
        ev = self.ops[min(self.ops)]
        out = []
        for a, b in gaps(*merge(ev.start, ev.end, self.lo, self.hi),
                         self.lo, self.hi):
            out.append([self.host_label((a + b) / 2), float(b - a) * 1e-9])
        out.sort(key=lambda g: -g[1])
        return out[:top]

    def host_label(self, t: float) -> str:
        an = self.annotations
        open_ = np.nonzero((an.start <= t) & (an.end >= t))[0]
        if not len(open_):
            return "none"
        return an.name[open_[np.argmax(an.start[open_])]]

    def top_ops(self, top: int = 10) -> list[list]:
        """Device time by op name (numbered instances folded together),
        most first, over all chips."""
        tot: dict[str, float] = {}
        for ev in self.ops.values():
            w = ev.within(self.lo, self.hi)
            for n, a, b in zip(w.name, w.start, w.end):
                key = re.sub(r"\.\d+$", "", n)
                tot[key] = tot.get(key, 0.0) + float(b - a) * 1e-9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:top]

    def kernel(self, name: str) -> Events:
        """Events of one kernel across chips, in the window, by start."""
        rows = []
        for ev in self.ops.values():
            w = ev.within(self.lo, self.hi).matching(op_pattern(name))
            rows += list(zip(w.name, w.start, w.end))
        return Events.of(rows)

    def program(self, fn: str) -> Events:
        rows = []
        for ev in self.modules.values():
            w = ev.within(self.lo, self.hi).matching(module_pattern(fn))
            rows += list(zip(w.name, w.start, w.end))
        return Events.of(rows)


def find_xplane(log_dir: str | Path) -> Path | None:
    files = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    return Path(files[-1]) if files else None


def load(path: str | Path, annotation_names) -> DeviceTrace | None:
    """The device trace of one profiler file, or None when it holds no
    device plane or no annotation to bound the window."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, modules, notes = {}, {}, []
    names = set(annotation_names)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[chip] = Events.of(
                        [(instruction(e.name), e.start_ns,
                          e.start_ns + e.duration_ns) for e in line.events])
                elif line.name == MODULES_LINE:
                    modules[chip] = Events.of(
                        [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                notes += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name in names]
    if not ops or not notes:
        return None
    an = Events.of(notes)
    return DeviceTrace(ops=ops, modules=modules, annotations=an,
                       lo=float(an.start.min()), hi=float(an.end.max()))
