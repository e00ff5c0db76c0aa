"""What a client of the scheduler sees, observed from outside it.

A token is *delivered* when the host holds its value and the value of
every earlier token of its stream: the stream prefix a streaming client
could have printed. Stage-2 tokens enter ``sched.results`` as ``None`` and
get their value when the scheduler harvests their bucket, so delivery is
read by scanning the results after every ``step()``. A token is *emitted*
when its slot appends to the stream at all (its exit decision is made);
the FLOP count reads emissions.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


class Tracker:
    def __init__(self, stream_by_sid: dict, admit_order: List[int]):
        self.by_sid = stream_by_sid
        self.order = admit_order         # sids in submission order
        self.n_admitted = 0
        self.delivered: Dict[int, List[float]] = {}   # sid -> times
        self.emitted_at: Dict[int, int] = {}
        self.admitted_at: Dict[int, float] = {}
        self.emits: List[tuple] = []     # (time, position) per decision
        self.open: List[int] = []

    def scan(self, sched, now: float) -> None:
        n_adm = len(self.order) - len(sched.queue)
        for sid in self.order[self.n_admitted:n_adm]:
            self.delivered[sid] = []
            self.emitted_at[sid] = 0
            self.admitted_at[sid] = now
            self.open.append(sid)
        self.n_admitted = n_adm
        res = sched.results
        still = []
        for sid in self.open:
            lst, got = res[sid], self.delivered[sid]
            n_emit = len(lst)
            if n_emit > self.emitted_at[sid]:
                S = len(self.by_sid[sid].prompt)
                for i in range(max(self.emitted_at[sid], 1), n_emit):
                    self.emits.append((now, S + i - 1))
                self.emitted_at[sid] = n_emit
            i = len(got)
            while i < n_emit and lst[i] is not None:
                got.append(now)
                i += 1
            if i < self.by_sid[sid].n_tokens:
                still.append(sid)
        self.open = still

    # -- end-to-end readings --------------------------------------------------

    def tokens_delivered(self, lo: float, hi: float) -> int:
        return sum(int(np.sum((np.asarray(t) >= lo) & (np.asarray(t) <= hi)))
                   for t in self.delivered.values())

    def ttft_s(self, lo: float, hi: float) -> List[float]:
        """First-token delivery minus due time, for every request due in
        [lo, hi) that was delivered a first token at all."""
        out = []
        for sid, r in self.by_sid.items():
            if lo <= r.due < hi and self.delivered.get(sid):
                out.append(self.delivered[sid][0] - r.due)
        return out

    def due_without_first(self, lo: float, hi: float) -> int:
        return sum(1 for sid, r in self.by_sid.items()
                   if lo <= r.due < hi and not self.delivered.get(sid))

    def itl_s(self, lo: float, hi: float) -> List[float]:
        """Gaps between consecutive deliveries of one stream, for every gap
        that ends in [lo, hi]."""
        out = []
        for t in self.delivered.values():
            a = np.asarray(t)
            if len(a) > 1:
                g = np.diff(a)
                end = a[1:]
                out.extend(g[(end >= lo) & (end <= hi)].tolist())
        return out

    def counts_between(self, lo: float, hi: float) -> dict:
        """Decisions and mean context emitted, and prompts admitted, in
        [lo, hi] — what the FLOP count of a traced window needs."""
        pos = [p for t, p in self.emits if lo <= t <= hi]
        prompts = [len(self.by_sid[s].prompt)
                   for s, t in self.admitted_at.items() if lo <= t <= hi]
        return {"decisions": len(pos),
                "mean_context": float(np.mean(pos)) + 1.0 if pos else 0.0,
                "prompt_lens": prompts}
