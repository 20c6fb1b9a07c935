#!/usr/bin/env python3
"""The engine's own spans and the step programs' named scopes, read from the
profiler trace of a ``--trace 1`` run.

``devtrace`` reduces the trace to device busy time, programs, kernels and
the harness's ``bench.*`` spans. This module reads what the program itself
puts into the same trace, on the same clock: every host span whose name
starts with ``engine.`` (``src/repro/engine/spans.py``), with its counters
as the event's stats, and the device time of each op per program, over the
program executions that start in the window. An op event carries only its
HLO instruction; ``scope_map`` reads the named scope of each instruction
from the compiled program's text (``op_name`` metadata), and
``program_scopes`` compiles the engine's programs at the window's shapes to
get it.

The readers built on this module (``engine_host_ms``, ``chunk_fill``,
``chunk_attention_ms``) give None where the trace holds no engine span at
all, a program from before the spans and scopes. Where it holds them but
lacks the span, counter or scope a reader needs, the reader raises
LookupError, as ``devtrace.Context`` does for a renamed program.

Offline, the window's breakdown with idle gaps named by the innermost engine
span and hot ops by scope, and the share of ``engine.step`` time that no
child span covers:

    python3 bench/enginetrace.py .bench_cache/trace/internlm2.chat

(the scope map is read from ``scopes.json`` beside the trace, which the
``chunk_attention_ms`` reader writes).
"""
from __future__ import annotations

import bisect
import functools
import gzip
import json
import os
import re
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import devtrace

# where bench/run.py writes the trace of a cell's window
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_cache", "trace")
SCOPES_FILE = "scopes.json"
ENGINE_PREFIX = "engine."
STEP_SPAN = "engine.step"
# named scopes of the step programs: kernels/ops.py names every op,
# models/transformer.py a block's attention and mlp (or moe) and the head.
# A device op belongs to the innermost of these in its op_name.
SCOPES = frozenset({
    "attention", "mlp", "moe", "lm_head", "flash_attention",
    "decode_attention", "paged_decode_attention", "paged_verify_attention",
    "paged_chunk_attention", "pq_scan"})
UNSCOPED = "unscoped"
# the harness's spans that name an idle gap no engine span holds
HARNESS_SPANS = ("bench.engine_iter", "bench.arrivals", "bench.accounting",
                 "bench.idle_wait")

Span = Tuple[int, int, dict]


def program_label(module: str) -> str:
    """``jit__chunk(12)`` -> ``chunk`` (a key of ``devtrace.PROGRAMS``); any
    other module by its name without the id: ``jit_broadcast_in_dim``."""
    for key, pat in devtrace.PROGRAMS.items():
        if pat.search(module):
            return key
    return module.split("(", 1)[0]


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=(]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Op name -> innermost of ``SCOPES`` in its ``op_name``, for every
    instruction of a compiled program's text (``compile().as_text()``)
    that has one. Instruction names are those the trace's op events carry."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        n = m and _OP_NAME.search(line, m.end())
        if n:
            for part in reversed(n.group(1).split("/")):
                if part in SCOPES:
                    out[m.group(1)] = part
                    break
    return out


def program_scopes(eng) -> Dict[str, Dict[str, str]]:
    """``scope_map`` of each step program of ``eng``: its jitted ``_decode``
    and ``_chunk`` (the modules ``devtrace.PROGRAMS`` matches), lowered at
    the shapes the engine dispatches and compiled, which loads the
    executables the window ran from the persistent cache."""
    import jax.numpy as jnp
    b, c = eng.max_batch, eng.chunk_size
    lowered = {"decode": eng._decode.lower(
        eng.params, jnp.zeros((b, 1), jnp.int32), eng.caches)}
    if c:
        lowered["chunk"] = eng._chunk.lower(
            eng.params, jnp.zeros((b, c), jnp.int32),
            jnp.zeros((b,), jnp.int32), eng.caches)
    return {k: scope_map(low.compile().as_text())
            for k, low in lowered.items()}


@dataclass
class EngineTrace:
    window: Tuple[int, int]
    engine: Dict[str, List[Span]]        # engine.* name -> spans, by start
    harness: Dict[str, List[Tuple[int, int]]]  # bench.* name -> intervals
    busy: List[Tuple[int, int]]          # chip 0, merged op intervals
    # (program_label, op) -> (calls, ns): chip 0's ops, over the program
    # executions that start in the window
    program_ops: Dict[Tuple[str, str], Tuple[int, int]]

    def engine_spans(self, name: str) -> List[Span]:
        """``name`` spans that start in the window."""
        lo, hi = self.window
        return [s for s in self.engine.get(name, []) if lo <= s[0] < hi]

    def spans_within(self, name: str, lo: int, hi: int) -> List[Span]:
        """``name`` spans that lie inside [lo, hi]."""
        return [s for s in self.engine.get(name, [])
                if lo <= s[0] and s[1] <= hi]

    def scoped(self, scope: str, program: str, scopes: dict) -> int:
        """Nanoseconds of ``program``'s ops that ``scopes`` (a scope map
        per program) puts under named scope ``scope``."""
        ops = scopes.get(program, {})
        return sum(t for (p, op), (_, t) in self.program_ops.items()
                   if p == program and ops.get(op) == scope)

    def step_self_share(self) -> Optional[float]:
        """Share of the time in the window's ``engine.step`` spans that no
        engine span inside them covers."""
        steps = self.engine_spans(STEP_SPAN)
        if not steps:
            return None
        inner = sorted((a, b) for n, ss in self.engine.items()
                       if n != STEP_SPAN for a, b, _ in ss)
        starts = [a for a, _ in inner]
        total = covered = 0
        for a, b, _ in steps:
            total += b - a
            kids = [(x, y) for x, y in inner[bisect.bisect_left(starts, a):
                                             bisect.bisect_left(starts, b)]
                    if y <= b]
            covered += sum(y - x for x, y in devtrace._union(kids))
        return 1.0 - covered / total

    def _innermost(self, t: int) -> Optional[str]:
        best = None
        for name, ss in self.engine.items():
            for a, b, _ in ss:
                if a <= t < b and (best is None or (a, -b) > best[0]):
                    best = ((a, -b), name)
        return best[1] if best else None

    def breakdown(self, scopes: dict) -> dict:
        """The hot device ops of chip 0 named ``op [scope]`` (or
        ``[unscoped]``), and the longest idle gaps named by the innermost
        engine span at their midpoint, else by the harness's span there."""
        by_op: Dict[str, int] = {}
        for (prog, op), (_, t) in self.program_ops.items():
            if not devtrace.CONTAINERS.match(op):
                key = f"{op} [{scopes.get(prog, {}).get(op, UNSCOPED)}]"
                by_op[key] = by_op.get(key, 0) + t
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:devtrace.TOP]
        lo, hi = self.window
        gaps, prev = [], lo
        for a, b in devtrace._clip(self.busy, lo, hi) + [(hi, hi)]:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        gaps.sort(key=lambda g: g[0] - g[1])
        named = []
        for a, b in gaps[:devtrace.TOP]:
            mid = (a + b) // 2
            where = self._innermost(mid) or next(
                (n for n in HARNESS_SPANS
                 if any(s <= mid < e for s, e in self.harness.get(n, []))),
                "harness")
            named.append([where, (b - a) / 1e9])
        return {"device_ops": [[n, t / 1e9] for n, t in top],
                "idle_gaps": named}


def read_profile(pd, path: str = "trace") -> EngineTrace:
    """Read a ``ProfileData``; a trace with no chip (a CPU run's) gives the
    host spans only."""
    engine: Dict[str, List[Span]] = {}
    harness: Dict[str, List[Tuple[int, int]]] = {}
    devices = []
    for plane in pd.planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ENGINE_PREFIX):
                    engine.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
                elif e.name.startswith("bench."):
                    harness.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    if devtrace.WINDOW_SPAN not in harness:
        raise ValueError(f"{path}: no {devtrace.WINDOW_SPAN} span")
    window = harness[devtrace.WINDOW_SPAN][0]
    for ss in engine.values():
        ss.sort(key=lambda s: s[0])
    busy, program_ops = [], {}
    if devices:
        chip0 = min(devices, key=lambda p: int(p.name.rsplit(":", 1)[1]))
        busy, program_ops = _chip_ops(chip0, window)
    return EngineTrace(window, engine, harness, busy, program_ops)


def _chip_ops(plane, window):
    """A chip's merged busy intervals, and each op's (calls, ns) by program,
    over the program executions that start in ``window``."""
    runs, ops = [], []
    for line in plane.lines:
        if line.name == devtrace.MODULE_LINE:
            runs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events)
        elif line.name == devtrace.OP_LINE:
            ops = [(e.start_ns, e.duration_ns, e.name) for e in line.events]
    starts = [a for a, _, _ in runs]
    lo, hi = window
    out: Dict[Tuple[str, str], List[int]] = {}
    for a, ns, name in ops:
        k = bisect.bisect_right(starts, a) - 1
        if k < 0 or a >= runs[k][1] or not lo <= runs[k][0] < hi:
            continue
        c = out.setdefault((program_label(runs[k][2]),
                            devtrace.op_name(name)), [0, 0])
        c[0] += 1
        c[1] += ns
    busy = devtrace._union([(a, a + ns) for a, ns, _ in ops])
    return busy, {k: (v[0], v[1]) for k, v in out.items()}


def read_file(path: str) -> EngineTrace:
    """Read one ``.xplane.pb`` (or a gzip of one)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return read_profile(ProfileData.from_serialized_xspace(f.read()),
                                path)
    return read_profile(ProfileData.from_file(path), path)


@functools.lru_cache(maxsize=1)
def _read_once(path: str, mtime: float) -> EngineTrace:
    return read_file(path)


def trace_dir(ctx) -> str:
    return os.path.join(TRACE_ROOT, ctx.spec["workload"]["name"])


def for_run(ctx) -> Optional[EngineTrace]:
    """The engine spans of this run's trace (read once for every reader of
    the run); None where the run wrote no trace."""
    try:
        path = devtrace.find_xplane(trace_dir(ctx))
    except FileNotFoundError:
        return None
    return _read_once(path, os.path.getmtime(path))


def scopes_for_run(ctx) -> Dict[str, Dict[str, str]]:
    """``program_scopes`` of this run's engine, written beside its trace for
    the offline breakdown."""
    scopes = program_scopes(ctx.feeder.eng)
    d = trace_dir(ctx)
    if os.path.isdir(d):
        with open(os.path.join(d, SCOPES_FILE), "w") as f:
            json.dump(scopes, f, sort_keys=True)
    return scopes


def main(argv) -> int:
    """Arguments: a trace dir or an ``.xplane.pb[.gz]``, then optionally
    a scope map (default: ``scopes.json`` in the trace dir)."""
    where = argv[0]
    path, scopes_path = where, None
    if os.path.isdir(where):
        path = devtrace.find_xplane(where)
        scopes_path = os.path.join(where, SCOPES_FILE)
    if len(argv) > 1:
        scopes_path = argv[1]
    scopes = {}
    if scopes_path and os.path.exists(scopes_path):
        with open(scopes_path) as f:
            scopes = json.load(f)
    et = read_file(path)
    print(json.dumps({"step_self_share": et.step_self_share(),
                      "breakdown": et.breakdown(scopes)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
