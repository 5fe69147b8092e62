"""Reduction of a profiler trace (`.xplane.pb`) and of the timed program's
HLO text to device numbers.

  * busy / idle: the union of the intervals in which an XLA module ran on
    each TPU (the "XLA Modules" line), over the traced window, which runs
    from the first module's start to the last one's end on that chip;
  * steps: the executions of the timed step's module, and the span from
    the first one's start to the last one's end;
  * self time by source file: device time of leaf ops (not `while`,
    `call` or `conditional`, whose events contain their bodies' ops),
    each op joined by name to its HLO instruction, and through the
    instruction's stack frame to every source file on its Python stack.
    A fusion is attributed through its own metadata, which XLA takes from
    the fusion's root.
"""
from __future__ import annotations

import collections
import glob
import os
import re

import numpy as np

NESTING_OPS = ("while", "call", "conditional")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"[\s}\]]([a-z][\w\-]*)\(")
_FRAME_ID = re.compile(r"stack_frame_id=(\d+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TABLE_ROW = re.compile(r'^(\d+)\s+(.*)$')
_FIELD = re.compile(r"(\w+)=(\d+)")


def parse_hlo(text):
    """{instruction name: (opcode, [source files on its stack, innermost
    first], op_name)} from an HLO module's text with its stack-frame
    tables."""
    files, locs, frames = {}, {}, {}
    section = None
    instrs = {}
    for line in text.splitlines():
        s = line.strip()
        if s in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            section = s
            continue
        if not s:
            section = None
            continue
        if section:
            m = _TABLE_ROW.match(s)
            if not m:
                continue
            key, rest = int(m.group(1)), m.group(2)
            if section == "FileNames":
                files[key] = rest.strip('"')
            elif section == "FileLocations":
                locs[key] = dict((k, int(v)) for k, v in _FIELD.findall(rest))
            elif section == "StackFrames":
                frames[key] = dict((k, int(v)) for k, v in _FIELD.findall(rest))
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        op = _OPCODE.search(" " + rhs)
        fid = _FRAME_ID.search(rhs)
        opn = _OP_NAME.search(rhs)
        instrs[name] = (op.group(1) if op else "",
                        int(fid.group(1)) if fid else 0,
                        opn.group(1) if opn else "")

    def chain(fid):
        out, seen = [], set()
        # a frame's printed parent is the parent's id plus one; 0 is none
        while fid and fid in frames and fid not in seen:
            seen.add(fid)
            loc = locs.get(frames[fid].get("file_location_id", 0), {})
            f = files.get(loc.get("file_name_id", 0))
            if f:
                out.append(f)
            fid = frames[fid].get("parent_frame_id", 0) - 1
        return out

    return {n: (op, chain(fid), opn) for n, (op, fid, opn) in instrs.items()}


def _op_name(event_name):
    return event_name.split(" = ")[0].strip().lstrip("%")


def find_xplane(logdir):
    files = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {logdir}, "
                           f"found {files}")
    return files[0]


def _union(intervals):
    busy, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    return busy


class DeviceTrace:
    """The TPU planes of one trace, reduced. Times in seconds.

    `planes` maps a plane name to {"modules": [(name, start_ns, end_ns)],
    "ops": [(name, start_ns, dur_ns)]}; `host` holds the host threads'
    events as (name, start_ns, end_ns)."""

    def __init__(self, planes, host=()):
        self.planes = planes
        self.host = list(host)
        if not planes or not any(p["modules"] for p in planes.values()):
            raise RuntimeError("the trace holds no XLA module on a TPU")

    @classmethod
    def from_file(cls, path):
        import jax
        data = jax.profiler.ProfileData.from_file(path)
        planes, host = {}, []
        for p in data.planes:
            lines = {ln.name: ln for ln in p.lines}
            if p.name.startswith("/device:TPU:") and "XLA Modules" in lines:
                planes[p.name] = {
                    "modules": [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in lines["XLA Modules"].events],
                    "ops": [(_op_name(e.name), e.start_ns, e.duration_ns)
                            for e in (lines["XLA Ops"].events
                                      if "XLA Ops" in lines else ())]}
            elif p.name.startswith("/host:CPU"):
                for ln in p.lines:
                    host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in ln.events)
        return cls(planes, host)

    # -- busy and idle -------------------------------------------------------

    def _extent(self, plane):
        mods = plane["modules"]
        return min(m[1] for m in mods), max(m[2] for m in mods)

    def window_s(self):
        """Mean over chips of the traced window."""
        ws = [np.subtract(*self._extent(p)[::-1]) for p in self.planes.values()
              if p["modules"]]
        return float(np.mean(ws)) / 1e9

    def busy_s(self):
        """Mean over chips of the time in which a module ran."""
        bs = [_union([(m[1], m[2]) for m in p["modules"]])
              for p in self.planes.values() if p["modules"]]
        return float(np.mean(bs)) / 1e9

    def idle_gaps(self, top=10):
        """The longest gaps between modules on the first chip, each named
        by the shortest host event that covers half of it or more (the
        most specific thing the host was doing), else by the one that
        overlaps it most."""
        plane = self.planes[sorted(self.planes)[0]]
        mods = sorted((m[1], m[2]) for m in plane["modules"])
        gaps, end = [], mods[0][1]
        for s, e in mods[1:]:
            if s > end:
                gaps.append((end, s))
            end = max(end, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            over = [(min(e, he) - max(s, hs), he - hs, hn)
                    for hn, hs, he in self.host if min(e, he) > max(s, hs)]
            cover = [o for o in over if o[0] >= 0.5 * (e - s)]
            if cover:
                name = min(cover, key=lambda o: o[1])[2]
            elif over:
                name = max(over)[2]
            else:
                name = "no host event"
            out.append([name, (e - s) / 1e9])
        return out

    # -- steps -----------------------------------------------------------------

    def step_runs(self, module_name):
        """Executions of the module named `module_name` on the first chip:
        (count, seconds from the first one's start to the last one's end)."""
        plane = self.planes[sorted(self.planes)[0]]
        runs = [m for m in plane["modules"]
                if m[0] == module_name or m[0].startswith(module_name + "(")
                or m[0].startswith(module_name + ".")]
        if not runs:
            return 0, 0.0
        return len(runs), (max(m[2] for m in runs)
                           - min(m[1] for m in runs)) / 1e9

    # -- ops ---------------------------------------------------------------------

    def leaf_self_time(self, hlo):
        """{op name: seconds} over leaf ops of the first chip, for ops
        that the HLO text names (other modules' ops are left out)."""
        plane = self.planes[sorted(self.planes)[0]]
        out = collections.Counter()
        for name, _, dur in plane["ops"]:
            name = _op_name(name)
            info = hlo.get(name)
            if info is None or info[0] in NESTING_OPS:
                continue
            out[name] += dur / 1e9
        return out

    def self_time_in_file(self, hlo, suffix):
        """Seconds of leaf ops whose Python stack passes through a file
        whose path ends with `suffix`."""
        times = self.leaf_self_time(hlo)
        return sum(t for name, t in times.items()
                   if any(f.endswith(suffix) for f in hlo[name][1]))

    def top_ops(self, hlo, top=10):
        """The leaf ops that took most time, each named with the end of its
        JAX op name and the innermost source file on its stack."""
        out = []
        for n, t in self.leaf_self_time(hlo).most_common(top):
            _, files, opn = hlo[n]
            where = os.path.basename(files[0]) if files else "?"
            out.append([f"{n} {'/'.join(opn.split('/')[-2:])} [{where}]", t])
        return out
