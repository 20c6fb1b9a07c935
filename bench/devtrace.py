"""Reduce a profiler trace (``.xplane.pb``) to device busy time, program and
kernel device time, and host spans.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. On a TPU each chip is a plane named
``/device:TPU:<n>``: its ``XLA Modules`` line holds one event per program
execution, its ``XLA Ops`` line one per operation. The harness's own spans
(``jax.profiler.TraceAnnotation``) are events on a host thread's line. All
of them share one clock (nanoseconds).

Programs and kernels are matched by name here and nowhere else
(``PROGRAMS``, ``KERNELS``). A traced window whose iterations ran a
program that the reduction cannot find raises: a rename then breaks the
benchmark loudly instead of reading as zero.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Tuple

# the engine jits steps.serve_step as ``_decode`` and steps.chunk_step as
# ``_chunk``; XLA names their modules ``jit__decode`` / ``jit__chunk``
PROGRAMS = {"decode": re.compile(r"^jit__decode(\W|$)"),
            "chunk": re.compile(r"^jit__chunk(\W|$)")}
# the Pallas paged decode kernel (kernels/paged_attention.py): its
# custom-call op carries the wrapper's name
KERNELS = {"paged_decode": re.compile(r"^paged_decode_attention(\.\d+)?$")}
# control flow whose span covers the ops of its body
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
TOP = 10


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def op_name(event_name: str) -> str:
    """``%fusion.7 = bf16[...] fusion(...)`` -> ``fusion.7``: an op event is
    named by its whole HLO instruction."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


@dataclass
class Reduction:
    window: Tuple[int, int]                       # traced window, ns
    busy: List[List[Tuple[int, int]]]             # per chip, merged op intervals
    modules: List[Tuple[str, int, int]]           # (name, start, end), chip 0
    ops: Dict[str, Tuple[int, int]]               # name -> (calls, ns), all chips
    spans: Dict[str, List[Tuple[int, int]]]       # host span name -> intervals
    chips: int = 1

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        lo, hi = self.window
        tot = sum(sum(b - a for a, b in _clip(iv, lo, hi)) for iv in self.busy)
        return tot / max(1, len(self.busy)) / 1e9

    def busy_between(self, lo: int, hi: int, chip: int = 0) -> int:
        """Nanoseconds of chip ``chip``'s busy time inside [lo, hi)."""
        return sum(b - a for a, b in _clip(self.busy[chip], lo, hi))

    def host_spans(self, name: str) -> List[Tuple[int, int]]:
        lo, hi = self.window
        return [(a, b) for a, b in self.spans.get(name, []) if lo <= a < hi]

    def program(self, key: str) -> Tuple[int, float]:
        """(calls, device seconds) of program ``key`` inside the window."""
        pat = PROGRAMS[key]
        lo, hi = self.window
        hits = [(a, b) for n, a, b in self.modules
                if pat.search(n) and lo <= a < hi]
        return len(hits), sum(b - a for a, b in hits) / 1e9

    def kernel(self, key: str) -> Tuple[int, float]:
        """(calls, device seconds, summed over chips) of kernel ``key``."""
        pat = KERNELS[key]
        calls, ns = 0, 0
        for name, (c, t) in self.ops.items():
            if pat.search(op_name(name)):
                calls += c
                ns += t
        return calls, ns / 1e9

    def breakdown(self) -> dict:
        """The device operations that took most time, and the longest idle
        gaps on chip 0 named by the host span they fall in."""
        by_op: Dict[str, int] = {}
        for name, (_, t) in self.ops.items():
            short = op_name(name)
            if not CONTAINERS.match(short):
                by_op[short] = by_op.get(short, 0) + t
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
        lo, hi = self.window
        busy = _clip(self.busy[0], lo, hi) if self.busy else []
        gaps, prev = [], lo
        for a, b in busy + [(hi, hi)]:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        gaps.sort(key=lambda g: g[0] - g[1])
        named = []
        for a, b in gaps[:TOP]:
            mid = (a + b) // 2
            where = "harness"
            for name in ("bench.engine_iter", "bench.arrivals",
                         "bench.accounting", "bench.idle_wait"):
                if any(s <= mid < e for s, e in self.spans.get(name, [])):
                    where = name
                    break
            named.append([where, (b - a) / 1e9])
        return {"device_ops": [[n, t / 1e9] for n, t in top],
                "idle_gaps": named}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_file(path: str) -> Reduction:
    """Reduce one ``.xplane.pb`` (or a gzip of one)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return reduce_profile(ProfileData.from_serialized_xspace(f.read()),
                                  path)
    return reduce_profile(ProfileData.from_file(path), path)


def reduce_profile(pd, path: str = "trace") -> Reduction:
    busy, modules, spans = [], [], {}
    ops: Dict[str, List[int]] = {}
    devices = sorted((p for p in pd.planes if DEVICE_PLANE.match(p.name)),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    for i, plane in enumerate(devices):
        intervals = []
        for line in plane.lines:
            if line.name == OP_LINE:
                for e in line.events:
                    a = e.start_ns
                    intervals.append((a, a + e.duration_ns))
                    c = ops.setdefault(e.name, [0, 0])
                    c[0] += 1
                    c[1] += e.duration_ns
            elif line.name == MODULE_LINE and i == 0:
                for e in line.events:
                    modules.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns))
        busy.append(_union(intervals))
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    if WINDOW_SPAN not in spans:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    window = spans[WINDOW_SPAN][0]
    return Reduction(window=window, busy=busy, modules=modules,
                     ops={k: (v[0], v[1]) for k, v in ops.items()},
                     spans=spans, chips=len(devices))


def reduce_dir(trace_dir: str) -> Reduction:
    return reduce_file(find_xplane(trace_dir))


@dataclass
class Context:
    """What a per-layer metric reader may read."""
    reduction: Reduction
    feeder: object
    window: object
    shape: object            # the architecture's Shape (bench/archs/)
    peak: dict
    chips: int
    spec: dict

    def window_iterations(self):
        return [it for it in self.feeder.iterations
                if self.window.inside(it.end)]

    def program(self, key: str) -> Tuple[int, float]:
        calls, seconds = self.reduction.program(key)
        ran = self._host_ran(key)
        if ran and not calls:
            raise LookupError(
                f"the window ran the {key} program but the trace has no "
                f"module matching {PROGRAMS[key].pattern}: was it renamed?")
        return calls, seconds

    def kernel(self, key: str) -> Tuple[int, float]:
        calls, seconds = self.reduction.kernel(key)
        if self._host_ran("decode") and not calls:
            raise LookupError(
                f"the window ran decode passes but the trace has no op "
                f"matching {KERNELS[key].pattern}: was the kernel renamed, "
                f"or did the op fall back to its reference?")
        return calls, seconds

    def _host_ran(self, key: str) -> bool:
        its = self.window_iterations()
        if key == "decode":
            return any(it.decode_lengths for it in its)
        return any(it.prompt_tokens for it in its)
