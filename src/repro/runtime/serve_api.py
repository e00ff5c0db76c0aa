"""Transport-agnostic serving API: the one admission surface every server
construction path and every serving front end programs against.

The scheduler's admission interface grew organically across PRs 4-6
(``submit``/``run``/``results`` plus the drift controller's actuator
setters), which was fine while there was exactly one scheduler on one
(sub)mesh. A fleet router (``runtime/router.py``) that owns admission
across N replicas needs that surface cut as an explicit contract, not an
implementation detail, so replicas can be in-process schedulers today and
multi-process / multi-host proxies tomorrow without touching the router.
This module owns that contract:

  * ``validate_request`` — THE submit-side validation (n_tokens >= 1,
    prompt + n_tokens within the pool's max_len, duplicate sample ids).
    One definition, byte-identical error messages, shared by the
    continuous scheduler and the fleet router — a
    malformed request is rejected at whichever surface sees it first,
    with the same error either way.
  * ``RequestQueue`` — the admission queue abstraction: FIFO arrival
    order, validated pushes, sid bookkeeping, revocation (the router's
    preemption primitive — only *unadmitted* requests can be revoked, so
    re-queueing never perturbs an in-flight token stream), and a copy
    protocol so live migration can snapshot/restore it like any other
    host container.
  * ``ReplicaHandle`` — the protocol a routable replica implements:
    ``submit``/``step``/``drain``/``results``/``stats`` plus the control
    actuators (``set_c_thr``/``request_capacity``/``request_migration``)
    and the load/feed introspection the router's policies consume
    (``n_slots``/``n_busy``/``queue_len``/``next_arrival``/
    ``revoke_queued``/``drain_finished``). ``ContinuousScheduler``
    implements it; the router depends ONLY on it.
  * ``build`` — the one construction entry point: decode serving (the
    continuous scheduler over the paged stage-2 store), prefill serving,
    and the host-loop oracles of both.

``step()`` returns one of three strings — the replica state machine the
router (and ``drain``) drives:

  * ``"busy"``    — the replica made progress (ticked, drained, admitted);
  * ``"waiting"`` — queued work exists but nothing is admissible yet (the
    caller owns the clock and should advance it toward
    ``next_arrival()``);
  * ``"idle"``    — queue and pool are fully drained.

This module deliberately imports nothing from the runtime at module scope
(``build`` resolves its factories lazily), so the scheduler can depend on
``RequestQueue`` without an import cycle.
"""
from __future__ import annotations

from collections import deque
from typing import (Callable, Deque, Dict, Iterator, List, Optional,
                    Protocol, Sequence, runtime_checkable)

__all__ = ["ReplicaHandle", "RequestQueue", "build", "validate_request"]


def validate_request(req, *, max_len: Optional[int] = None,
                     is_dup: Optional[Callable[[int], bool]] = None) -> None:
    """Submit-side request validation — the single shared definition (and
    the single set of error messages) for every admission surface.

    ``max_len`` bounds ``len(prompt) + n_tokens`` (None = unbounded);
    ``is_dup(sid)`` reports whether
    the surface has already seen the sample id (queued, admitted or
    finished)."""
    if req.n_tokens < 1:
        raise ValueError(f"n_tokens must be >= 1, got {req.n_tokens}")
    if max_len is not None and len(req.prompt) + req.n_tokens > max_len:
        raise ValueError(
            f"request {req.sample_id}: S + n_tokens = "
            f"{len(req.prompt) + req.n_tokens} exceeds pool max_len "
            f"{max_len}")
    if is_dup is not None and is_dup(req.sample_id):
        raise ValueError(f"duplicate sample id {req.sample_id}")


class RequestQueue:
    """Validated FIFO admission queue over ``Request`` objects.

    Owns the sid membership set that the duplicate check reads, so "is
    this sample id already queued?" has exactly one source of truth. The
    deque interface (``append``/``popleft``/``__getitem__``/iteration)
    matches what the schedulers' admission loops already used, so the
    queue drops in where a bare ``deque`` lived.

    ``revoke`` is the router's preemption primitive: remove specific
    *unadmitted* requests (admitted ones are no longer here — a pop is
    the admission boundary) and hand them back, preserving arrival order
    among the survivors. ``__copy__`` gives live migration the same
    shallow-snapshot semantics the bare containers had.
    """

    def __init__(self, max_len: Optional[int] = None,
                 is_dup: Optional[Callable[[int], bool]] = None):
        self.max_len = max_len
        self._is_dup = is_dup
        self._q: Deque = deque()
        self._queued: set = set()

    # -- validated push ------------------------------------------------------

    def append(self, req) -> None:
        """Validate and enqueue (arrival order = queue order). Rejects a
        malformed request before it can damage in-flight state."""
        validate_request(
            req, max_len=self.max_len,
            is_dup=lambda sid: sid in self._queued
            or (self._is_dup is not None and self._is_dup(sid)))
        self._queued.add(req.sample_id)
        self._q.append(req)

    # -- admission pops / inspection -----------------------------------------

    def popleft(self):
        req = self._q.popleft()
        self._queued.discard(req.sample_id)
        return req

    def __len__(self) -> int:
        return len(self._q)

    def __bool__(self) -> bool:
        return bool(self._q)

    def __getitem__(self, i):
        return self._q[i]

    def __iter__(self) -> Iterator:
        return iter(self._q)

    def __contains__(self, sample_id: int) -> bool:
        return sample_id in self._queued

    def next_arrival(self) -> Optional[float]:
        """The HEAD request's arrival_time (None when empty) — admission
        is FIFO, so this is the time that unblocks the next admission;
        a clock-owning caller advances toward it when ``step`` reports
        ``"waiting"``."""
        if not self._q:
            return None
        return self._q[0].arrival_time

    # -- revocation (preemption / degrade redistribution) --------------------

    def revoke(self, sample_ids: Optional[Sequence[int]] = None) -> List:
        """Remove and return queued (UNADMITTED) requests by sample id —
        ``None`` revokes everything. Requests already popped for admission
        are untouched (and absent from the return), which is what makes
        fleet-level preemption stream-preserving: a revoked request has
        never emitted a token."""
        want = None if sample_ids is None else set(sample_ids)
        taken, kept = [], deque()
        for r in self._q:
            if want is None or r.sample_id in want:
                taken.append(r)
                self._queued.discard(r.sample_id)
            else:
                kept.append(r)
        self._q = kept
        return taken

    # -- snapshot protocol (live migration) ----------------------------------

    def __copy__(self) -> "RequestQueue":
        new = RequestQueue(self.max_len, self._is_dup)
        new._q = deque(self._q)
        new._queued = set(self._queued)
        return new


@runtime_checkable
class ReplicaHandle(Protocol):
    """What a routable serving replica looks like. ``ContinuousScheduler``
    implements it in-process; the fleet router
    depends only on this surface, so a transport proxy (multi-process,
    multi-host) that speaks it routes identically.

    Beyond the admission core (``submit``/``step``/``drain``/``results``/
    ``stats``) and the control actuators, the protocol carries the load
    and event-feed introspection the routing policies need: pool
    geometry, live occupancy, queue depth, revocation, and the
    per-request finish feed (sid + realized per-request hardness — the
    tenant-difficulty signal ``drift_aware`` routing learns from)."""

    clock: object
    results: Dict[int, List[int]]

    # -- admission core ------------------------------------------------------

    def submit(self, req) -> None: ...

    def step(self) -> str: ...

    def drain(self) -> Dict[int, List[int]]: ...

    @property
    def stats(self): ...

    # -- control actuators ---------------------------------------------------

    def set_c_thr(self, c_thr: float) -> None: ...

    def request_capacity(self, capacity: int) -> None: ...

    def request_migration(self, plan) -> None: ...

    # -- load / feed introspection (routing policies) ------------------------

    @property
    def n_slots(self) -> int: ...

    @property
    def n_busy(self) -> int: ...

    @property
    def queue_len(self) -> int: ...

    def next_arrival(self) -> Optional[float]: ...

    def revoke_queued(self,
                      sample_ids: Optional[Sequence[int]] = None) -> List: ...

    def drain_finished(self) -> List: ...


# ---------------------------------------------------------------------------
# unified construction: one entry point for every serving mode
# ---------------------------------------------------------------------------

_MODES = ("prefill", "decode")


def build(params, cfg, spec, sc, *, mode: str = "decode",
          scheduler: Optional[str] = "continuous", placement=None,
          n_slots: Optional[int] = None, max_len: Optional[int] = None,
          clock=None, host: bool = False, page_size: Optional[int] = None,
          n_pages: Optional[int] = None, events=None):
    """Build a serving object — the single construction path
    ``launch/serve.py``, the benchmarks and the examples share.

    ==========  ======  ===================================================
    mode        host    returns
    ==========  ======  ===================================================
    "decode"    False   ``ContinuousScheduler`` over the paged stage-2
                        store (needs ``n_slots``, ``max_len`` and
                        ``page_size``; carries the ``fns_factory`` live
                        migration rebuilds stage callables with)
    "decode"    True    ``HostLoopDecoder`` — the dense host-loop decode,
                        the reference the scheduler's streams are checked
                        against
    "prefill"   False   ``TwoStageServer`` — batch-level EE serving
    "prefill"   True    ``HostLoopServer`` — its host-loop reference
    ==========  ======  ===================================================

    ``scheduler`` is "continuous" for the scheduler and None for the other
    three; any other value raises. ``placement`` disaggregates the two
    stages onto disjoint submeshes for the device-resident objects;
    ``clock`` shares a time base across replicas — REQUIRED when the
    scheduler joins a ``FleetRouter`` fleet.

    ``page_size`` sizes the pages of the stage-2 KV pool (``max_len`` must
    be a multiple); the scheduler allocates pages on admit and frees them
    on finish over ``n_pages`` allocatable pages (default:
    ``n_slots * max_len / page_size`` — pass less to serve more slots than
    a full-width store could hold at the same HBM budget)."""
    # ``events`` wires a telemetry.EventLog request-lifecycle feed into the
    # scheduler — the observability plane (runtime/observe.Tracer /
    # StatsSampler) subscribes to it.
    from repro.runtime import serve_loop as SL

    if mode not in _MODES:
        raise ValueError(f"mode must be one of {_MODES}, got {mode!r}")
    want = None if host or mode == "prefill" else "continuous"
    if scheduler != want:
        raise ValueError(
            f"mode={mode!r} host={host} takes scheduler={want!r}, got "
            f"{scheduler!r} (decode serving is the continuous scheduler; "
            "prefill serving and the host-loop oracles have none)")
    if want is None:
        if page_size is not None or n_pages is not None or events is not None:
            raise ValueError("page_size / n_pages / events configure the "
                             "continuous scheduler's page pool and request "
                             "lifecycle feed")
        if mode == "prefill":
            if host:
                return SL.HostLoopServer(*SL._stage_fns(params, cfg, spec),
                                         sc)
            s1, s2 = SL._stage_fns(params, cfg, spec, placement)
            return SL.TwoStageServer(s1, s2, sc, placement)
        return SL.HostLoopDecoder(SL.decode_stage_fns(params, cfg, spec), sc)
    if n_slots is None or max_len is None or page_size is None:
        raise ValueError("the continuous scheduler needs n_slots, max_len "
                         "(the pool's shared cache width) and page_size")
    return SL.ContinuousScheduler(
        SL.decode_stage_fns(params, cfg, spec, placement,
                            page_size=page_size), sc,
        n_slots=n_slots, max_len=max_len, placement=placement, clock=clock,
        n_pages=n_pages, events=events,
        fns_factory=lambda pl: SL.decode_stage_fns(params, cfg, spec, pl,
                                                   page_size=page_size))
