"""The device trace of a traced run: capture between engine steps, load
into a compact form, and reduce to what the per-layer readers need.

Compact form (what ``load`` returns and the committed test trace holds)::

    {"devices": {plane: {"ops": [[name, start_ns, dur_ns, custom], ...],
                         "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

``ops`` are the device's "XLA Ops" events (HLO name), ``custom`` true for
a call of a Pallas kernel (``tpu_custom_call``); ``modules`` its "XLA Modules" events, one per
program execution; ``host`` the benchmark's own ``bench.*`` spans
(``jax.profiler.TraceAnnotation`` around the engine step, the scheduler,
the runner's calls, the front door's worker hop, the KV commit).

Device work is attributed to a runner call by the host span open around
it, not by program names: every runner call ends by copying its logits to
the host, so the programs of one ``bench.decode`` span are that decode
call's. Within a kind of call, custom-call time is also kept by kernel
name (the HLO op name up to its last ``.<n>``: ``paged_decode_attention``,
``chunked_prefill_attention``), so that a kernel's reader reads its own
time and no other kernel's.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

HOST_PREFIX = "bench."
RUNNER_SPANS = {"decode": "bench.decode", "prefill": "bench.prefill"}


@dataclass
class TraceSummary:
    window_s: float                       # first traced step start .. last end
    busy_s: float                         # union of op intervals, mean over chips
    calls: Dict[str, int]                 # runner calls per kind
    program_s: Dict[str, float]           # device module time per kind
    kernels: Dict[str, Dict[str, float]]  # custom-call time per kind, by
    #                                       kernel name
    top_ops: List[Tuple[str, float]]      # most device time, by leaf op
    idle_by_host: List[Tuple[str, float]]  # idle time, by innermost host span
    n_devices: int = 1
    notes: List[str] = field(default_factory=list)


def _custom(name: str) -> bool:
    """A Pallas kernel: an HLO custom call to the TPU's kernel target."""
    return 'custom_call_target="tpu_custom_call"' in name


def _short(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def kernel_name(op: str) -> str:
    """``paged_decode_attention.9`` -> ``paged_decode_attention``."""
    return re.sub(r"\.\d+$", "", op)


def _parents(ops) -> set:
    """Indices of ops that enclose another op (a loop around its body)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    stack, out = [], set()
    for i in order:
        s, e = ops[i][1], ops[i][2]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            out.add(stack[-1])
        stack.append(i)
    return out


def load(xplane_path: str) -> dict:
    """Compact events of one ``.xplane.pb``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        ops.append([_short(e.name), int(e.start_ns),
                                    int(e.duration_ns), _custom(e.name)])
                elif line.name == "XLA Modules":
                    for e in line.events:
                        mods.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
            if ops:
                devices[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"devices": devices, "host": host}


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _innermost(spans: List[Tuple[str, int, int]], t: float) -> str:
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "none"


def reduce(compact: dict, top: int = 10) -> Optional[TraceSummary]:
    """Window, busy time, per-kind runner program time and kernel time by
    name, top device ops and idle time by host span. ``None`` when the
    trace holds no traced step or no device op."""
    host = [(n, s, s + d) for n, s, d in compact["host"]]
    steps = [(s, e) for n, s, e in host if n == "bench.step"]
    if not steps or not compact["devices"]:
        return None
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    window = hi - lo
    busy_total, op_time, idle = 0.0, {}, {}
    calls = {k: sum(1 for n, s, e in host if n == v and lo <= s < hi)
             for k, v in RUNNER_SPANS.items()}
    prog = {k: 0.0 for k in RUNNER_SPANS}
    by_name: Dict[str, Dict[str, float]] = {k: {} for k in RUNNER_SPANS}
    kind_spans = {k: _union([(s, e) for n, s, e in host if n == v])
                  for k, v in RUNNER_SPANS.items()}
    edges = sorted({t for _, s, e in host for t in (s, e)})

    def kind_of(t):
        for k, iv in kind_spans.items():
            for s, e in iv:
                if s <= t < e:
                    return k
        return None

    for dev in compact["devices"].values():
        ops = [(n, s, s + d, c) for n, s, d, c in dev["ops"]
               if s + d > lo and s < hi]
        busy = _union(_clip([(s, e) for _, s, e, _ in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy) / 1e9
        parents = _parents(ops)
        for i, (n, s, e, c) in enumerate(ops):
            d = (min(e, hi) - max(s, lo)) / 1e9
            if i not in parents:
                op_time[n] = op_time.get(n, 0.0) + d
            k = kind_of((s + e) / 2)
            if k is not None and c:
                name = kernel_name(n)
                by_name[k][name] = by_name[k].get(name, 0.0) + d
        for n, s, d in dev["modules"]:
            k = kind_of(s + d / 2)
            if k is not None and lo <= s < hi:
                prog[k] += min(d, hi - s) / 1e9
        prev = lo
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                # split the gap where a host span opens or closes
                i, j = (bisect.bisect_right(edges, prev),
                        bisect.bisect_left(edges, s))
                pts = [prev] + edges[i:j] + [s]
                for a, b in zip(pts, pts[1:]):
                    name = _innermost(host, (a + b) / 2)
                    idle[name] = idle.get(name, 0.0) + (b - a) / 1e9
            prev = max(prev, e)
    n_dev = len(compact["devices"])
    return TraceSummary(
        window_s=window / 1e9, busy_s=busy_total / n_dev, calls=calls,
        program_s=prog, kernels=by_name,
        top_ops=sorted(op_time.items(), key=lambda x: -x[1])[:top],
        idle_by_host=sorted(idle.items(), key=lambda x: -x[1])[:top],
        n_devices=n_dev)
