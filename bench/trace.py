"""Reduction of a profiler trace (``.xplane.pb``) to device time.

The harness wraps the measured window in a ``bench.window`` span and marks
what the host does inside it (``bench.step`` around each trainer step,
``bench.dispatch`` around the jitted call, ``bench.batch`` around batch
making).  This module reads the device operations of each TPU, the union of
their intervals inside the window (busy time), the idle gaps between them
labelled by the host span they fall in, and the Pallas kernels of the
compiled step, found by the source function that each ``tpu_custom_call``
in its HLO was traced from.
"""
from __future__ import annotations

import base64
import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


@dataclass
class Event:
    name: str
    start: int          # ns, on the trace's common timeline
    dur: int            # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclass
class Trace:
    devices: Dict[int, List[Event]]     # TPU id -> its operations
    host: List[Event]                   # the harness's own spans
    modules: Dict[int, List[Event]]     # TPU id -> the programs it ran

    def window(self) -> Tuple[int, int]:
        spans = [e for e in self.host if e.name == "bench.window"]
        if len(spans) != 1:
            raise ValueError(f"expected one bench.window span, found {len(spans)}")
        return spans[0].start, spans[0].end


def instruction_name(text: str) -> str:
    """``"%fusion.3 = bf16[..] fusion(...), ..."`` -> ``"fusion.3"``: the TPU
    trace names an operation by its HLO instruction's text."""
    m = _INSTR.match(text)
    return m.group(1) if m else text


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    Event(instruction_name(e.name), int(e.start_ns),
                          int(e.duration_ns)) for e in line.events)
            elif m and line.name == MODULES_LINE:
                modules.setdefault(int(m.group(1)), []).extend(
                    Event(e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events)
            elif not m and plane.name.startswith("/host:"):
                host.extend(Event(e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    for evs in list(devices.values()) + list(modules.values()):
        evs.sort(key=lambda e: e.start)
    host.sort(key=lambda e: e.start)
    return Trace(devices, host, modules)


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {directory}, "
                         f"found {len(paths)}")
    return paths[0]


def intervals(events: List[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Union of the events' intervals, clipped to ``[lo, hi)``, sorted."""
    out: List[Tuple[int, int]] = []
    for e in sorted(events, key=lambda e: e.start):
        a, b = max(e.start, lo), min(e.end, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(events: List[Event], lo: int, hi: int) -> int:
    return sum(b - a for a, b in intervals(events, lo, hi))


def host_label(host: List[Event], a: int, b: int) -> str:
    """The innermost harness span that holds the middle of ``[a, b)``;
    inside ``bench.step`` but outside its dispatch and batch, the trainer
    is reading the step's metrics back."""
    mid = (a + b) // 2
    inner = [e for e in host if e.start <= mid < e.end]
    if not inner:
        return "outside any span"
    name = min(inner, key=lambda e: e.dur).name
    return "bench.step (metrics read-back)" if name == "bench.step" else name


def idle_gaps(trace: Trace, device: int, top: int = 10) -> List[Tuple[str, float]]:
    """The ``top`` longest gaps (s) between device operations in the
    window, each labelled with what the host was doing."""
    lo, hi = trace.window()
    busy = intervals(trace.devices.get(device, []), lo, hi)
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [(host_label(trace.host, a, b), (b - a) * 1e-9) for a, b in gaps[:top]]


# ---------------------------------------------------------------------------
# The compiled step's HLO: which instruction is which kernel
# ---------------------------------------------------------------------------

_TABLE = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)$")
_ARRAY = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\](\{[^}]*\})?")


@dataclass
class Array:
    dtype: str
    shape: Tuple[int, ...]
    space: int          # memory space: 0 is HBM, 1 the chip's on-core memory

    @property
    def nbytes(self) -> int:
        n = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1,
             "s8": 1, "u8": 1}[self.dtype]
        for d in self.shape:
            n *= d
        return n


def _array(text: str) -> Optional[Array]:
    m = _ARRAY.match(text.strip())
    if not m:
        return None
    space = re.search(r"S\((\d+)\)", m.group(3) or "")
    return Array(m.group(1), tuple(int(d) for d in m.group(2).split(",") if d),
                 int(space.group(1)) if space else 0)


@dataclass
class Kernel:
    instruction: str
    functions: Tuple[str, ...]     # source functions it was traced from
    result: Array
    operands: List[Array]


def instruction_lines(hlo: str) -> Dict[str, str]:
    """Each instruction's line of an HLO module, by name."""
    out = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = line
    return out


def _stack_frames(hlo: str):
    """Stack frame id -> the function names of its chain, innermost first."""
    tables: Dict[str, Dict[int, str]] = {}
    current = None
    for line in hlo.splitlines():
        if _TABLE.match(line.strip()):
            current = tables.setdefault(line.strip(), {})
            continue
        if current is not None:
            m = re.match(r"^(\d+) (.*)$", line.strip())
            if m:
                current[int(m.group(1))] = m.group(2)
                continue
            current = None
    fn_names = {k: v.strip('"') for k, v in tables.get("FunctionNames", {}).items()}
    loc_fn = {k: int(re.search(r"function_name_id=(\d+)", v).group(1))
              for k, v in tables.get("FileLocations", {}).items()}
    frames = {}
    for k, v in tables.get("StackFrames", {}).items():
        loc = int(re.search(r"file_location_id=(\d+)", v).group(1))
        parent = int(re.search(r"parent_frame_id=(\d+)", v).group(1))
        frames[k] = (loc, parent)

    def chain(fid: int) -> Tuple[str, ...]:
        out, seen = [], set()
        while fid in frames and fid not in seen:
            seen.add(fid)
            loc, parent = frames[fid]
            out.append(fn_names.get(loc_fn.get(loc, -1), "?"))
            fid = parent
        return tuple(out)
    return chain


def _body_functions(line: str) -> Tuple[str, ...]:
    """Names in the debug locations of a kernel's serialized Mosaic body:
    the Python call stack the ``pallas_call`` was made from, which the HLO's
    own stack frame may cut short."""
    m = re.search(r'"body":"([A-Za-z0-9+/=]*)"', line)
    if not m:
        return ()
    body = base64.b64decode(m.group(1))
    return tuple(sorted({w.decode() for w in
                         re.findall(rb"[A-Za-z_][A-Za-z0-9_]{2,}", body)}))


def pallas_kernels(hlo: str) -> Dict[str, Kernel]:
    """``tpu_custom_call`` instructions of an HLO module, by name: the
    source functions they were made in, and their result and operands
    (shape and memory space)."""
    lines = instruction_lines(hlo)
    chain = _stack_frames(hlo)
    kernels = {}
    for name, line in lines.items():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        call = re.search(r" = (.*?) custom-call\(([^)]*)\)", line)
        operands = [_array(lines[arg][_INSTR.match(lines[arg]).end():])
                    for arg in re.findall(r"%([\w.\-]+)", call.group(2))]
        sf = re.search(r"stack_frame_id=(\d+)", line)
        kernels[name] = Kernel(
            name, (chain(int(sf.group(1))) if sf else ()) + _body_functions(line),
            _array(call.group(1)), operands)
    return kernels


def module_name(hlo: str) -> str:
    m = re.match(r"HloModule ([\w.\-]+)", hlo)
    return m.group(1) if m else ""


def self_times(events: List[Event], lo: int, hi: int) -> Dict[str, float]:
    """Seconds per operation name inside ``[lo, hi)``, each event's time
    less that of the events nested in it (a loop's body ops)."""
    out: Dict[str, float] = {}
    stack: List[Event] = []
    for e in events:                               # sorted by start
        if not (lo <= e.start and e.end <= hi):
            continue
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack and e.end <= stack[-1].end:
            parent = stack[-1]
            out[parent.name] = out.get(parent.name, 0.0) - e.dur * 1e-9
        out[e.name] = out.get(e.name, 0.0) + e.dur * 1e-9
        stack.append(e)
    return out


def kernel_events(trace: Trace, hlo: str, function: str
                  ) -> Optional[List[Tuple[Event, Kernel]]]:
    """Every operation in the window, on every TPU, of the compiled step's
    kernels that were traced from the source function ``function``; None
    where the step has no such kernel or the trace shows none of its
    calls."""
    kernels = {k: v for k, v in pallas_kernels(hlo).items()
               if function in v.functions}
    if not kernels:
        return None
    lo, hi = trace.window()
    runs = {d: intervals([m for m in mods if m.name.startswith(module_name(hlo) + "(")],
                         lo, hi) for d, mods in trace.modules.items()}
    out = []
    for d, evs in trace.devices.items():
        spans = runs.get(d, [])
        out.extend((e, kernels[e.name]) for e in evs if e.name in kernels and any(
            a <= e.start and e.end <= b for a, b in spans))
    return out or None
