"""Reduction of a ``jax.profiler`` trace to what the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` a traced run wrote and keeps, as plain
JSON-able lists, the traced window, the device ops and programs of every
chip, and the host annotations; ``Reduced`` turns those lists into busy
time, per-program and per-kernel device time, and idle gaps named by what
the host was doing. The split keeps the arithmetic testable on a small
recorded trace (``tests/data``) without a chip.

Device ops are the events of the ``XLA Ops`` line of each ``/device:TPU:n``
plane; programs are the ``XLA Modules`` line. On a CPU backend (tests) the
ops are the host-thread events that carry an ``hlo_module`` stat.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench_window"
_MODULE_ID = re.compile(r"\(\d+\)$")
_OP_ID = re.compile(r"\.\d+$")
# ops whose interval holds other ops of the same line: kept for busy time,
# left out of the per-op ranking so nothing is counted twice
CONTAINERS = ("while", "conditional", "call")


def module_name(name: str) -> str:
    """``jit__pool_tick_fused(123)`` -> ``jit__pool_tick_fused``."""
    return _MODULE_ID.sub("", name).strip()


def op_name(name: str) -> str:
    """``%fusion.12 = (bf16[..]) fusion(..)`` -> ``fusion``."""
    head = name.lstrip("%").split(" ", 1)[0].split("=", 1)[0]
    return _OP_ID.sub("", head)


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:     # a stat the binding cannot convert: none kept
        return {}


def extract(logdir: str, host_spans: Tuple[str, ...]) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    chips: Dict[str, dict] = {}
    host: List[list] = []
    cpu_ops: List[list] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "NON_CORE" not in \
                plane.name:
            chip = chips.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    chip[key].append([ev.start_ns, ev.end_ns, ev.name])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host_spans:
                        host.append([ev.start_ns, ev.end_ns, ev.name])
                    elif ev.duration_ns > 0:
                        st = _stats(ev)
                        if "hlo_module" in st:
                            cpu_ops.append([ev.start_ns, ev.end_ns, ev.name,
                                            str(st["hlo_module"])])
    if not chips and cpu_ops:             # CPU backend: one "chip"
        chips["/host:CPU"] = {
            "ops": [o[:3] for o in cpu_ops],
            "modules": [[o[0], o[1], o[3]] for o in cpu_ops]}
    win = [h for h in host if h[2] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return {"window": [win[0][0], win[0][1]],
            "chips": [chips[k] for k in sorted(chips)],
            "host": [h for h in host if h[2] != WINDOW_SPAN]}


def _clip(iv, lo, hi):
    out = []
    for s, e, *rest in iv:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append([s, e, *rest])
    return out


def union(intervals) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e, *_ in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over chips
    busy_s_per_chip: List[float]
    module_s: Dict[str, float]          # summed over chips
    module_n: Dict[str, int]
    op_s: Dict[str, float]
    op_n: Dict[str, int]
    op_in_module_s: Dict[str, float]    # "program:op" -> seconds
    idle_by_host: Dict[str, float]      # mean over chips

    @classmethod
    def of(cls, tr: dict) -> "Reduced":
        lo, hi = tr["window"]
        busy, idle = [], {}
        module_s, module_n, op_s, op_n, op_mod = {}, {}, {}, {}, {}
        host = sorted(tr["host"])
        for chip in tr["chips"]:
            ops = sorted(_clip(chip["ops"], lo, hi))
            mods = sorted(_clip(chip["modules"], lo, hi))
            spans = union(ops)
            busy.append(sum(e - s for s, e in spans) * 1e-9)
            j = 0
            for s, e, name in ops:
                base = op_name(name)
                if base in CONTAINERS:
                    continue
                op_s[base] = op_s.get(base, 0.0) + (e - s) * 1e-9
                op_n[base] = op_n.get(base, 0) + 1
                while j < len(mods) and mods[j][1] <= s:
                    j += 1
                prog = (module_name(mods[j][2]).removeprefix("jit_")
                        if j < len(mods) and mods[j][0] <= s else "?")
                key = f"{prog}:{base}"
                op_mod[key] = op_mod.get(key, 0.0) + (e - s) * 1e-9
            for s, e, name in mods:
                m = module_name(name)
                module_s[m] = module_s.get(m, 0.0) + (e - s) * 1e-9
                module_n[m] = module_n.get(m, 0) + 1
            edges = [lo] + [x for iv in spans for x in iv] + [hi]
            for gs, ge in zip(edges[::2], edges[1::2]):
                if ge > gs:
                    name = _host_at(host, gs, ge)
                    idle[name] = idle.get(name, 0.0) + (ge - gs) * 1e-9
        n = max(len(tr["chips"]), 1)
        return cls(window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / n,
                   busy_s_per_chip=busy, module_s=module_s,
                   module_n=module_n, op_s=op_s, op_n=op_n,
                   op_in_module_s=op_mod,
                   idle_by_host={k: v / n for k, v in idle.items()})

    def modules_matching(self, *needles: str) -> Tuple[float, int]:
        s = sum(v for k, v in self.module_s.items()
                if any(x in k for x in needles))
        n = sum(v for k, v in self.module_n.items()
                if any(x in k for x in needles))
        return s, n

    def ops_matching(self, *prefixes: str) -> Tuple[float, int]:
        s = sum(v for k, v in self.op_s.items() if k.startswith(prefixes))
        n = sum(v for k, v in self.op_n.items() if k.startswith(prefixes))
        return s, n

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_in_module_s.items(),
                     key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _host_at(host: list, gs: float, ge: float) -> str:
    """The innermost host annotation that covers most of a gap."""
    best, best_len, best_cov = "untraced host", None, 0.0
    for s, e, name in host:
        if s >= ge:
            break
        cov = min(e, ge) - max(s, gs)
        if cov <= 0:
            continue
        if cov > best_cov * 1.0001 or (cov >= best_cov * 0.9999 and (
                best_len is None or e - s < best_len)):
            best, best_len, best_cov = name, e - s, cov
    return best
