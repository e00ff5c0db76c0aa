"""Live-migration state machine: zero-dropped-request stream equivalence
across capacity re-sizes and full chip re-splits, clean rollback from a
fault in any stage, quiesce bounding, device-loss degradation, and (under
hypothesis) random fault point x stage invariants.

Chaos-sweep compatibility: the CI chaos job re-runs this file with
``REPRO_FAULT_PLAN`` armed. Tests asserting an exact migration outcome
shadow the ambient plan via ``faults.installed``; the ambient-facing tests
assert only invariants that hold whether the sweep's fault fired here or
not (streams exact, no hang, admission re-opened).
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax

from test_scheduler import _toy_expected, _toy_requests, toy_decode_fns, _TOY_S
from repro.core.stage_mesh import StageMeshPlan
from repro.runtime import faults
from repro.runtime import scheduler as S
from repro.runtime.migration import (LiveMigrator, MigrationError,
                                     MigrationPlan, QuiesceTimeout,
                                     migrate_on_device_loss)
from repro.runtime.scheduler import ContinuousScheduler, LogicalClock
from repro.runtime.stage_executor import StagePlacement

_REPO_ROOT = str(Path(__file__).resolve().parent.parent)

_N_TOKS = [6, 3, 8, 5, 2, 7, 4, 6]


def _sched(fns, *, placement=None, capacity=2, fns_factory=None,
           mig_after=None, plan=None):
    """Toy-fns scheduler with all requests submitted; ``mig_after`` arms
    ``plan`` from the controller hook after that many pool ticks — the
    migration then applies at the next discrete re-plan point."""
    sc = S.ServeConfig(capacity=capacity, queue_depth=2, c_thr=0.5)
    sched = ContinuousScheduler(fns, sc, n_slots=4, max_len=_TOY_S + 8,
                                clock=LogicalClock(), placement=placement,
                                fns_factory=fns_factory)
    if mig_after is not None:
        class _Trig:
            ticks = 0

            def on_tick(self, s, nd, nh, conf):
                self.ticks += 1
                if self.ticks == mig_after:
                    s.request_migration(plan)
        sched.controller = _Trig()
    for r in _toy_requests(_N_TOKS):
        sched.submit(r)
    return sched


# ---------------------------------------------------------------------------
# the contract: migrated streams bitwise-equal to an unmigrated run
# ---------------------------------------------------------------------------

def test_capacity_migration_stream_equivalence():
    fns = toy_decode_fns(q_pct=40)
    with faults.installed(None):
        sched = _sched(fns, mig_after=3,
                       plan=MigrationPlan(capacity=3, reason="test"))
        res = sched.run()
    assert res == _toy_expected(_N_TOKS)            # zero dropped/duplicated
    st = sched.stats
    assert st.n_migrations == 1 and st.n_migration_rollbacks == 0
    assert sched.sc.capacity == 3
    assert 0.0 < st.migration_pause_p50_ms == st.migration_pause_p99_ms
    assert sched._admission_open


def test_migration_before_first_admission():
    """A plan armed before the pool warms up migrates the cold scheduler
    (no device state to re-place) and still serves correctly."""
    fns = toy_decode_fns(q_pct=40)
    with faults.installed(None):
        sched = _sched(fns)
        sched.request_migration(MigrationPlan(capacity=3, reason="cold"))
        res = sched.run()
    assert res == _toy_expected(_N_TOKS)
    assert sched.stats.n_migrations == 1 and sched.sc.capacity == 3


def test_migration_plan_validation():
    with pytest.raises(ValueError, match="placement"):
        MigrationPlan(fns=object())                 # fns without placement
    with pytest.raises(ValueError, match="quiesce_timeout_s"):
        MigrationPlan(quiesce_timeout_s=0.0)


# ---------------------------------------------------------------------------
# rollback: a fault in ANY stage restores the old plan, streams stay exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("point", ["migrate:quiesce", "migrate:snapshot",
                                   "migrate:replace", "migrate:resume"])
def test_rollback_from_each_stage_preserves_streams(point):
    fns = toy_decode_fns(q_pct=40)
    with faults.installed(faults.FaultPlan.parse(f"{point}@1")):
        sched = _sched(fns, mig_after=3,
                       plan=MigrationPlan(capacity=3, reason="test"))
        res = sched.run()
    assert res == _toy_expected(_N_TOKS)
    st = sched.stats
    assert st.n_migration_rollbacks == 1 and st.n_migrations == 0
    assert sched.sc.capacity == 2                   # old plan restored
    assert sched._admission_open


def test_rollback_restores_byte_identical_state():
    """Direct LiveMigrator rollback on a warm, drained pool: every device
    lane, the host metadata, and the plan objects come back exactly."""
    fns = toy_decode_fns(q_pct=40)
    with faults.installed(None):
        sched = _sched(fns)
        sched.run()                                 # warm + drained
    lanes = ("_tok", "_pos", "_active_lane", "_start_lane", "_budget_lane")
    before_dev = {a: np.asarray(getattr(sched, a)) for a in lanes}
    before_host = {a: list(getattr(sched, a))
                   for a in ("_sid", "_emitted", "_budget", "_state",
                             "_free")}
    before_refs = {a: getattr(sched, a)
                   for a in ("fns", "placement", "ex1", "ex2", "sc",
                             "ring")}
    with faults.installed(faults.FaultPlan.parse("migrate:replace@1")):
        with pytest.raises(MigrationError):
            LiveMigrator(sched, MigrationPlan(capacity=3,
                                              reason="test")).run()
    for a in lanes:
        assert np.array_equal(np.asarray(getattr(sched, a)),
                              before_dev[a]), a
    for a, want in before_host.items():
        assert list(getattr(sched, a)) == want, a
    for a, want in before_refs.items():
        assert getattr(sched, a) is want, a         # same objects restored
    assert sched._admission_open
    assert sched.stats.n_migration_rollbacks == 1


def test_quiesce_timeout_bounded_and_rolled_back():
    """A ring that cannot drain never reaches a shape-change-safe point:
    QUIESCE raises within its bounded wait instead of hanging, and the
    rollback re-opens admission."""
    fns = toy_decode_fns(q_pct=40)
    with faults.installed(None):
        sched = _sched(fns)
        sched.run()
        sched.ring.count = 1                        # wedge: claims a row
        sched._dispatch_bucket = lambda reason: None  # ...never drains
        with pytest.raises(MigrationError) as ei:
            LiveMigrator(sched, MigrationPlan(
                capacity=3, quiesce_timeout_s=0.05,
                reason="test")).run()
    assert isinstance(ei.value, QuiesceTimeout)
    assert sched._admission_open
    assert sched.stats.n_migration_rollbacks == 1


# ---------------------------------------------------------------------------
# transient runtime faults: retried, stream never notices
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [
    "dispatch@2#transient",
    "enqueue@1#transient",
    "dispatch@1#transient;enqueue@2#transient;dispatch@4#transient",
])
def test_transient_faults_survive(spec):
    fns = toy_decode_fns(q_pct=40)
    with faults.installed(faults.FaultPlan.parse(spec)):
        res = _sched(fns).run()
    assert res == _toy_expected(_N_TOKS)


def test_streams_exact_under_ambient_plan():
    """The chaos-sweep-facing test: runs with whatever REPRO_FAULT_PLAN the
    environment armed (none locally). Every survivable ambient fault —
    transient runtime faults, fatal migration-stage faults — must leave
    the streams exact and the server admitting."""
    fns = toy_decode_fns(q_pct=40)
    sched = _sched(fns, mig_after=3,
                   plan=MigrationPlan(capacity=3, reason="ambient"))
    res = sched.run()
    assert res == _toy_expected(_N_TOKS)
    st = sched.stats
    assert st.n_migrations + st.n_migration_rollbacks == 1
    assert sched._admission_open


# ---------------------------------------------------------------------------
# device loss
# ---------------------------------------------------------------------------

def test_device_loss_requires_factory_and_chips():
    fns = toy_decode_fns(q_pct=40)
    sched = _sched(fns)                             # single-device, no factory
    with pytest.raises(MigrationError, match="fns_factory"):
        migrate_on_device_loss(sched, [0])
    sched = _sched(fns, fns_factory=lambda pl: fns)
    with pytest.raises(MigrationError, match="no chips"):
        migrate_on_device_loss(sched, [0])


# ---------------------------------------------------------------------------
# hypothesis: random fault point x kind -> invariants
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st_h
    _HAVE_HYP = True
except ImportError:                                   # pragma: no cover
    _HAVE_HYP = False

_SURVIVABLE_POINTS = ["dispatch", "enqueue", "migrate:quiesce",
                      "migrate:snapshot", "migrate:replace",
                      "migrate:resume"]

if _HAVE_HYP:
    @settings(max_examples=12, deadline=None)
    @given(point=st_h.sampled_from(_SURVIVABLE_POINTS),
           nth=st_h.integers(min_value=1, max_value=6),
           transient=st_h.booleans(),
           mig_after=st_h.integers(min_value=1, max_value=6),
           q_pct=st_h.sampled_from([20, 40, 70]))
    def test_migration_invariants_random_fault(point, nth, transient,
                                               mig_after, q_pct):
        """Any survivable injected fault x any migration trigger point:
        no dropped or duplicated token (streams exact), the server ends
        admitting with a drained pool, exactly one migration attempt is
        accounted (done or rolled back), and a completed migration's pause
        is recorded under the (generous) budget."""
        if not transient and point in ("dispatch", "enqueue"):
            transient = True                        # fatal hot-loop faults
                                                    # are expected to kill
                                                    # the server, not be
                                                    # survived — tested in
                                                    # test_faults
        kind = "#transient" if transient else ""
        plan = MigrationPlan(capacity=3, pause_budget_ms=60_000.0,
                             reason="hyp")
        fns = toy_decode_fns(q_pct=q_pct)
        with faults.installed(faults.FaultPlan.parse(f"{point}@{nth}{kind}")):
            sched = _sched(fns, mig_after=mig_after, plan=plan)
            res = sched.run()
        assert res == _toy_expected(_N_TOKS)
        st = sched.stats
        assert st.n_migrations + st.n_migration_rollbacks == 1
        assert sched._admission_open
        if st.n_migrations:
            assert st.migration_pause_p99_ms < plan.pause_budget_ms
else:                                                 # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_migration_invariants_random_fault():
        pass


# ---------------------------------------------------------------------------
# disaggregated: full chip re-split on 8 host devices
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")
def test_chip_resplit_migration_8dev():
    """The tentpole acceptance bar (toy fns): a running disaggregated
    scheduler re-splits 4+4 -> 6+2 mid-serve; streams exact, placement
    swapped, one migration recorded."""
    fns = toy_decode_fns(q_pct=40)
    pl_a = StagePlacement.from_plan(StageMeshPlan.proportional(0.5, 8))
    pl_b = StagePlacement.from_plan(StageMeshPlan.proportional(0.25, 8))
    with faults.installed(None):
        sched = _sched(fns, placement=pl_a, fns_factory=lambda pl: fns,
                       mig_after=3,
                       plan=MigrationPlan(placement=pl_b, fns=fns,
                                          capacity=3, reason="resplit"))
        res = sched.run()
    assert res == _toy_expected(_N_TOKS)
    st = sched.stats
    assert st.n_migrations == 1
    assert (st.stage1_chips, st.stage2_chips) == (6, 2)
    assert sched.placement is not pl_a


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")
def test_chip_resplit_rollback_8dev():
    fns = toy_decode_fns(q_pct=40)
    pl_a = StagePlacement.from_plan(StageMeshPlan.proportional(0.5, 8))
    pl_b = StagePlacement.from_plan(StageMeshPlan.proportional(0.25, 8))
    with faults.installed(faults.FaultPlan.parse("migrate:replace@1")):
        sched = _sched(fns, placement=pl_a, fns_factory=lambda pl: fns,
                       mig_after=3,
                       plan=MigrationPlan(placement=pl_b, fns=fns,
                                          reason="resplit"))
        res = sched.run()
    assert res == _toy_expected(_N_TOKS)
    st = sched.stats
    assert st.n_migration_rollbacks == 1
    assert (st.stage1_chips, st.stage2_chips) == (4, 4)
    assert sched.placement is pl_a


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")
def test_device_loss_degrades_8dev():
    """Losing a stage-2 chip mid-serve degrades to a 7-chip split through
    the live migrator — streams exact, server alive."""
    fns = toy_decode_fns(q_pct=40)
    pl_a = StagePlacement.from_plan(StageMeshPlan.proportional(0.5, 8))
    with faults.installed(None):
        sched = _sched(fns, placement=pl_a, fns_factory=lambda pl: fns)

        class _Loss:
            ticks = 0

            def on_tick(self, s, nd, nh, conf):
                self.ticks += 1
                if self.ticks == 3:
                    migrate_on_device_loss(s, [s.ex2.devices[-1]],
                                           q=0.4)
        sched.controller = _Loss()
        res = sched.run()
    assert res == _toy_expected(_N_TOKS)
    st = sched.stats
    assert st.n_migrations == 1
    assert st.stage1_chips + st.stage2_chips == 7


def test_real_model_resplit_subprocess():
    """The full acceptance criterion: a REAL tiny EE model on an 8-device
    disaggregated ContinuousScheduler live-migrates through a full chip
    re-split (param re-slice via the attached fns_factory) and its streams
    stay bitwise-equal to the host-loop oracle."""
    code = ("import os\n"
            "os.environ['XLA_FLAGS']="
            "'--xla_force_host_platform_device_count=8'\n"
            "os.environ.pop('REPRO_FAULT_PLAN', None)\n"
            "import sys; sys.path.insert(0, 'src')\n" + textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import early_exit as ee
    from repro.core.stage_mesh import StageMeshPlan
    from repro.models.config import ArchConfig
    from repro.runtime import serve_loop as SL
    from repro.runtime.migration import MigrationPlan
    from repro.runtime.scheduler import LogicalClock, Request
    from repro.runtime.stage_executor import StagePlacement

    cfg = ArchConfig(name="t", family="dense", n_layers=4, d_model=32,
                     n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                     dtype="float32", param_dtype="float32",
                     tie_embeddings=True)
    spec0 = ee.EarlyExitSpec(exit_layer=2, c_thr=0.5)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec0)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(21), (6, 8),
                                           0, cfg.vocab))
    n_toks = [5, 3, 5, 1, 4, 2]
    conf = SL.decode_step0_confidences(params, cfg, spec0, prompt,
                                       max_len=13)
    c_thr = float(jnp.quantile(conf, 0.5))
    spec = ee.EarlyExitSpec(exit_layer=2, c_thr=c_thr)
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=c_thr)
    oracle = SL.build_host_decoder(params, cfg, spec, sc).generate(prompt, 5)
    want = {i: [int(x) for x in oracle["tokens"][i][:n_toks[i]]]
            for i in range(6)}
    pl_a = StagePlacement.from_plan(StageMeshPlan.proportional(0.5, 8))
    pl_b = StagePlacement.from_plan(StageMeshPlan.proportional(0.25, 8))
    s = SL.build_continuous_scheduler(params, cfg, spec, sc, n_slots=3,
                                      max_len=13, placement=pl_a,
                                      clock=LogicalClock())
    plan = MigrationPlan(placement=pl_b, fns=s.fns_factory(pl_b),
                         capacity=3, reason="resplit")
    class Trig:
        ticks = 0
        def on_tick(self, sch, nd, nh, c):
            self.ticks += 1
            if self.ticks == 2:
                sch.request_migration(plan)
    s.controller = Trig()
    for i in range(6):
        s.submit(Request(i, prompt[i], n_toks[i]))
    res = s.run()
    assert res == want, "migrated streams != oracle"
    assert s.stats.n_migrations == 1
    assert (s.stats.stage1_chips, s.stats.stage2_chips) == (6, 2)
    assert s.stats.migration_pause_p99_ms > 0.0
    print("RESPLIT_OK pause_ms", s.stats.migration_pause_p99_ms)
    """))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=_REPO_ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "RESPLIT_OK" in r.stdout
