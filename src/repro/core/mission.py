"""Mission sessions: composable stage-graph pipeline with streaming
contact windows.

A :class:`Mission` owns the persistent budget state of one satellite —
an :class:`~repro.core.energy.EnergyLedger` plus a downlink byte ledger
— and executes an explicit stage graph over ingested frame segments:

    ingest(frames):          Capture -> RoiFilter -> Dedup -> OnboardCount
    contact_window(bytes):   Select -> Downlink -> GroundRecount -> Aggregate

``ingest`` may be called any number of times (orbital passes); each call
grants the day-fraction energy/byte entitlement for its tile slice and
runs the onboard stages under whatever energy remains, so budgets carry
across passes. ``contact_window`` drains pending segments FIFO through
the ground-side stages within one window's byte budget (default: the
accumulated entitlement of the pending segments). ``result()``
aggregates everything windowed so far into a
:class:`~repro.core.pipeline.PipelineResult`; ``finalize()`` first
flushes pending segments through a zero-byte window (onboard-accepted
counts still land — nothing is transmitted).

Selection logic is pluggable: ``PipelineConfig.method`` names a
registered :class:`~repro.core.policies.SelectionPolicy`; the executor
itself has no per-method branching. Stages are objects too — pass custom
``ingest_stages`` / ``contact_stages`` lists to compose new graphs
without touching this module.

``run_pipeline(frames, space, ground, pcfg)`` remains as a compatibility
wrapper over a one-window Mission and is bit-identical to the
pre-refactor monolith on both the engine and reference paths (enforced
by tests/test_mission.py against the frozen oracle in
:mod:`repro.core._legacy`).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.dedup as dd
from repro.core import engine, obs
from repro.core.cascade import count_tiles_batched, count_tiles_batched_ref
from repro.core.energy import (ByteLedger, EnergyLedger, detector_gflops,
                               max_tiles_within_budget)
from repro.core.metrics import cmae
from repro.core.pipeline import PipelineConfig, PipelineResult, budgets_for
from repro.core.policies import PolicyContext, Selection, get_policy
from repro.core.throttle import clamp_budget_bytes


@dataclass
class Segment:
    """One ingested frame batch (an orbital pass's tile slice) and the
    per-tile state the stages accumulate over it."""
    frames: list
    energy_grant_override: Optional[float] = None
    # Capture
    n: int = 0
    prep: Optional[engine.PreparedFrames] = None
    tiles_sp: object = None          # device (engine) or host (reference)
    tiles_gd: object = None
    true: Optional[np.ndarray] = None
    energy_granted_j: float = 0.0
    byte_entitlement: float = 0.0
    # RoiFilter / Dedup
    active: Optional[np.ndarray] = None
    rep_of: Optional[np.ndarray] = None
    # OnboardCount
    conf: Optional[np.ndarray] = None
    counts_sp: Optional[np.ndarray] = None
    processed: Optional[np.ndarray] = None
    n_processed: int = 0
    # contact-window stages
    selection: Optional[Selection] = None
    counts_gd: Optional[np.ndarray] = None
    bytes_requested: float = 0.0
    bytes_spent: float = 0.0
    pred: Optional[np.ndarray] = None
    # fault/degradation state (repro.core.faults): transmission attempts
    # that failed, the retry-with-backoff bookkeeping, and whether the
    # last attempt's downlink was discarded by the ground
    retries: int = 0
    eligible_round: int = 0
    requeued: bool = False
    corrupted: bool = False


@dataclass
class ContactWindow:
    """Mutable byte budget shared by the segments of one window."""
    budget: float
    remaining: float


@dataclass
class IngestReport:
    n_frames: int
    n_tiles: int
    tiles_processed_space: int
    energy_granted_j: float
    energy_remaining_j: float
    byte_entitlement: float


@dataclass
class WindowReport:
    budget_bytes: float
    bytes_requested: float
    bytes_spent: float
    tiles_downlinked: int
    segments: int


class Stage:
    """One node of the Mission stage graph.

    Ingest stages are called as ``run(mission, seg)``; contact stages as
    ``run(mission, seg, window)``. Subclass and insert into
    ``Mission(ingest_stages=..., contact_stages=...)`` to extend the
    graph without touching core.
    """

    name = "stage"

    def run(self, mission: "Mission", seg: Segment,
            window: Optional[ContactWindow] = None) -> None:
        raise NotImplementedError


class Capture(Stage):
    """Tile + resize + moments (engine path: one fused device program),
    collect ground truth, and grant this slice's day-fraction budgets.

    Split into :meth:`prepare` (tiles/truth) and :meth:`admit` (budget
    grant + per-tile state init) so the fleet engine can substitute a
    constellation-batched prepare (shared frame buckets across
    satellites) and still run the exact admission arithmetic.
    """

    name = "capture"

    def run(self, mission, seg, window=None):
        self.prepare(mission, seg)
        self.admit(mission, seg)

    def prepare(self, mission, seg):
        pcfg = mission.pcfg
        sp_cfg = mission.space[1]
        gd_cfg = mission.ground[1]
        if not seg.frames:
            seg.n = 0
            seg.true = np.zeros(0, np.float64)
            seg.tiles_sp = np.zeros(
                (0, sp_cfg.input_size, sp_cfg.input_size, 3), np.float32)
            seg.tiles_gd = np.zeros(
                (0, gd_cfg.input_size, gd_cfg.input_size, 3), np.float32)
        elif pcfg.use_engine:
            # skip the fused program's moments/ROI tail when this
            # policy consumes neither statistic (tiles are identical)
            with_stats = ((pcfg.use_roi and mission.policy.wants_roi)
                          or (pcfg.use_dedup and mission.policy.wants_dedup))
            prep = engine.prepare_frames(seg.frames, pcfg.tile_size,
                                         sp_cfg.input_size, gd_cfg.input_size,
                                         with_stats=with_stats)
            seg.prep = prep
            seg.tiles_sp, seg.tiles_gd = prep.tiles_sp, prep.tiles_gd
            seg.true, seg.n = prep.true, prep.n
        else:
            from repro.core import tiling
            from repro.data.synthetic import tile_counts

            def prep_tiles(img, input_size):
                t = tiling.tile_image(jnp.asarray(img), pcfg.tile_size)
                return np.asarray(tiling.resize_tiles(t, input_size))

            sp, gd, true = [], [], []
            for img, boxes, _classes in seg.frames:
                true.append(tile_counts(boxes, img.shape[0], pcfg.tile_size))
                sp.append(prep_tiles(img, sp_cfg.input_size))
                gd.append(prep_tiles(img, gd_cfg.input_size))
            seg.tiles_sp = np.concatenate(sp)
            seg.tiles_gd = np.concatenate(gd)
            seg.true = np.concatenate(true).astype(np.float64)
            seg.n = seg.tiles_sp.shape[0]

    def admit(self, mission, seg):
        energy = self.entitle(mission, seg)
        mission.ledger.grant(energy)
        mission.ledger.charge_capture(len(seg.frames))
        self.init_state(mission, seg)

    @staticmethod
    def entitle(mission, seg) -> float:
        """Record the slice's day-fraction entitlements on the segment;
        returns the energy grant (the fleet engine grants a whole
        constellation's entitlements in one vectorized ledger op)."""
        energy, byte_budget, _ = budgets_for(mission.pcfg, seg.n)
        if seg.energy_grant_override is not None:
            energy = float(seg.energy_grant_override)
        seg.energy_granted_j = energy
        seg.byte_entitlement = byte_budget
        return energy

    @staticmethod
    def init_state(mission, seg):
        mission.frames_seen += len(seg.frames)
        seg.active = np.ones(seg.n, bool)
        seg.rep_of = np.arange(seg.n)
        seg.conf = np.full(seg.n, -1.0)
        seg.counts_sp = np.zeros(seg.n)
        seg.processed = np.zeros(seg.n, bool)


class RoiFilter(Stage):
    """Drop low-variance tiles (background/cloud) when the policy uses ROI."""

    name = "roi_filter"

    def run(self, mission, seg, window=None):
        pcfg = mission.pcfg
        if not (pcfg.use_roi and mission.policy.wants_roi) or seg.n == 0:
            return
        if seg.prep is not None:
            # stddev moment from the fused program; np.asarray is free
            # for the host copy and materializes a still-deferred device
            # slice (engine defer_stats) exactly here
            raw_sd = np.asarray(seg.prep.roi_std)
        else:
            raw_sd = np.asarray(jnp.mean(jnp.std(jnp.asarray(seg.tiles_sp),
                                                 axis=(1, 2)), axis=-1))
        seg.active &= raw_sd > pcfg.roi_std_thresh


class Dedup(Stage):
    """Cluster active tiles into geographic contexts; representatives
    stand for their cluster downstream."""

    name = "dedup"

    def run(self, mission, seg, window=None):
        pcfg = mission.pcfg
        if (not (pcfg.use_dedup and mission.policy.wants_dedup)
                or seg.active.sum() <= 4):
            return
        k = pcfg.k_clusters or max(2, int(seg.active.sum()) // 2)
        idx_active = np.where(seg.active)[0]
        if seg.prep is not None:
            # bucketed gather of the fused program's moments: pad the index
            # vector so the gather (and the whole dedup) is shape-stable
            n_act = len(idx_active)
            with obs.span("dedup.gather"):
                idx_pad = np.zeros(dd.dedup_pad_size(n_act), np.int64)
                idx_pad[:n_act] = idx_active
                moments = seg.prep.moments[jnp.asarray(idx_pad)]
            with obs.span("dedup.program"):
                res = dd.dedup_from_moments(moments, k,
                                            jax.random.PRNGKey(pcfg.seed),
                                            n=n_act)
        else:
            res = dd.dedup(jnp.asarray(seg.tiles_sp[idx_active]), k,
                           jax.random.PRNGKey(pcfg.seed))
        with obs.span("dedup.fetch"):
            assign = np.asarray(res.assign)
            rep_local = np.asarray(res.rep_idx)
        seg.rep_of[idx_active] = idx_active[rep_local[assign]]
        mission.ledger.charge_aggregate(len(idx_active))


class OnboardCount(Stage):
    """Energy-capped onboard counting of representatives (the paper's
    '22% of observable images' bottleneck), charged to the ledger."""

    name = "onboard_count"

    def run(self, mission, seg, window=None):
        if not mission.policy.wants_onboard:
            return
        pcfg = mission.pcfg
        reps = np.unique(seg.rep_of[seg.active])
        cap = max_tiles_within_budget(mission.ledger.remaining * 0.95,
                                      mission.gflops_space, pcfg.hardware)
        process = reps[:cap] if len(reps) > cap else reps
        seg.n_processed = len(process)
        mission.ledger.charge_compute(seg.n_processed, mission.gflops_space,
                                      pcfg.hardware)
        counts_sp = np.zeros(seg.n)
        conf = np.full(seg.n, -1.0)
        if seg.n_processed:
            with obs.span("count.space"):
                c, f = mission._count(mission.space, seg.tiles_sp, process)
            counts_sp[process] = c
            conf[process] = f
        seg.counts_sp = counts_sp[seg.rep_of]
        seg.conf = conf[seg.rep_of]
        seg.processed = np.isin(seg.rep_of, process) & seg.active


def policy_context(mission: "Mission", seg: Segment) -> PolicyContext:
    """Selection-time view of one segment — shared by the scalar Select
    stage and the batched ContactPlan executor's lane stacking, so both
    paths hand the policy bit-identical inputs."""
    return PolicyContext(n=seg.n, active=seg.active, rep_of=seg.rep_of,
                         conf=seg.conf, counts_sp=seg.counts_sp,
                         processed=seg.processed,
                         tile_bytes=mission.tile_bytes, pcfg=mission.pcfg)


class Select(Stage):
    """Delegate the accept/transmit/credit decision to the registered
    :class:`~repro.core.policies.SelectionPolicy`."""

    name = "select"

    def run(self, mission, seg, window=None):
        budget = window.remaining if window is not None else 0.0
        seg.selection = mission.policy.select(policy_context(mission, seg),
                                              budget)


class Downlink(Stage):
    """Charge the byte/radio ledgers; actual spend is capped by the
    window budget even when the policy is bandwidth-oblivious."""

    name = "downlink"

    def run(self, mission, seg, window=None):
        sel = seg.selection
        remaining = window.remaining if window is not None else 0.0
        spend = min(sel.bytes_requested, remaining)
        mission.ledger.charge_downlink(spend, mission.pcfg.bandwidth_mbps)
        if window is not None:
            # prefix-drain with the denormal/negative underflow clamp:
            # a remainder below one normal float of bytes is exact 0.0
            # (bit-exact no-op on any real budget — see throttle)
            window.remaining = clamp_budget_bytes(window.remaining - spend)
        seg.bytes_requested = sel.bytes_requested
        seg.bytes_spent = spend
        mission.bytes_ledger.requested += sel.bytes_requested
        mission.bytes_ledger.spent += spend


class GroundRecount(Stage):
    """Recount transmitted tiles with the deeper ground-tier counter."""

    name = "ground_recount"

    def run(self, mission, seg, window=None):
        counts_gd = np.zeros(seg.n)
        down = seg.selection.downlink
        if len(down):
            with obs.span("count.ground"):
                c, _ = mission._count(mission.ground, seg.tiles_gd, down)
            counts_gd[down] = c
        seg.counts_gd = counts_gd[seg.rep_of]


class Aggregate(Stage):
    """Fuse onboard and ground counts into per-tile predictions."""

    name = "aggregate"

    def run(self, mission, seg, window=None):
        sel = seg.selection
        pred = np.zeros(seg.n, np.float64)
        pred[sel.accept_space] = seg.counts_sp[sel.accept_space]
        pred[sel.ground_credit] = seg.counts_gd[sel.ground_credit]
        seg.pred = pred


def default_ingest_stages() -> List[Stage]:
    return [Capture(), RoiFilter(), Dedup(), OnboardCount()]


def default_contact_stages() -> List[Stage]:
    return [Select(), Downlink(), GroundRecount(), Aggregate()]


class Mission:
    """One satellite's pipeline session (see module docstring).

    Parameters
    ----------
    space, ground : (params, cfg) counter pairs (see ``get_counters``).
    pcfg : PipelineConfig — ``method`` names the registered selection
        policy; ``use_engine`` picks the device-resident vs reference
        execution of the counting stages.
    energy_cfgs : optional (space_cfg_full, ground_cfg_full) used to
        PRICE compute; defaults to the paper's full-scale Table II
        counters.
    ingest_stages, contact_stages : optional custom stage lists.
    """

    def __init__(self, space, ground, pcfg: PipelineConfig = None,
                 energy_cfgs=None, ingest_stages: List[Stage] = None,
                 contact_stages: List[Stage] = None):
        self.pcfg = pcfg if pcfg is not None else PipelineConfig()
        self.space = space
        self.ground = ground
        if energy_cfgs is None:
            from repro.configs import get_config
            energy_cfgs = (get_config("targetfuse-space"),
                           get_config("targetfuse-ground"))
        self.gflops_space = detector_gflops(energy_cfgs[0])
        self.policy = get_policy(self.pcfg.method)
        self.tile_bytes = float(self.pcfg.real_tile_px ** 2 * 3)
        self.ledger = EnergyLedger(budget_j=0.0)
        self.bytes_ledger = ByteLedger()
        self.frames_seen = 0
        self._finalized = False
        self.ingest_stages = (list(ingest_stages) if ingest_stages is not None
                              else default_ingest_stages())
        self.contact_stages = (list(contact_stages)
                               if contact_stages is not None
                               else default_contact_stages())
        self._segments: List[Segment] = []  # ingest order
        self._pending: List[Segment] = []   # awaiting a contact window

    # byte-ledger views (the stacked fleet ledger swaps in its own
    # bytes_ledger; these names stay stable for drivers/examples)
    @property
    def bytes_budget(self) -> float:
        """Bytes offered across contact windows."""
        return self.bytes_ledger.budget

    @property
    def bytes_requested(self) -> float:
        """Bytes the policies asked to transmit."""
        return self.bytes_ledger.requested

    @property
    def bytes_spent(self) -> float:
        """Bytes actually charged (<= budget)."""
        return self.bytes_ledger.spent

    # -- streaming API ------------------------------------------------------

    def ingest(self, frames, energy_budget_j: float = None, *,
               blackout: bool = False) -> IngestReport:
        """Run the onboard stages over one frame batch (an orbital pass).

        Grants the slice's day-fraction energy budget (or an explicit
        ``energy_budget_j``) to the persistent ledger first; onboard
        counting then runs under whatever energy remains mission-wide.

        ``blackout=True`` skips the pass entirely (a satellite brownout
        round injected by :mod:`repro.core.faults`): no segment is
        created, nothing is granted or charged — zero harvest, zero
        capture — and the mission's stream state is untouched.
        """
        if blackout:
            return IngestReport(
                n_frames=0, n_tiles=0, tiles_processed_space=0,
                energy_granted_j=0.0,
                energy_remaining_j=self.ledger.remaining,
                byte_entitlement=0.0)
        self._finalized = False
        seg = Segment(frames=list(frames),
                      energy_grant_override=energy_budget_j)
        with obs.span("mission.ingest"):
            for stage in self.ingest_stages:
                with obs.span("stage." + stage.name):
                    stage.run(self, seg)
        self._segments.append(seg)
        self._pending.append(seg)
        return IngestReport(
            n_frames=len(seg.frames), n_tiles=seg.n,
            tiles_processed_space=seg.n_processed,
            energy_granted_j=seg.energy_granted_j,
            energy_remaining_j=self.ledger.remaining,
            byte_entitlement=seg.byte_entitlement)

    def contact_window(self, budget_bytes: float = None) -> WindowReport:
        """Drain pending segments through the ground-side stages within
        one window's byte budget (default: the pending segments'
        accumulated entitlement). Segments are served FIFO; unspent
        budget flows to later segments in the same window.

        After :meth:`finalize` (and before any new ingest) this is a
        no-op: the mission is drained, so an offered window neither
        flushes anything nor inflates the byte-budget accounting."""
        if self._window_is_noop():
            return self._drained_window_report()
        with obs.span("mission.contact"):
            segs, window = self._open_window(budget_bytes)
            for seg in segs:
                for stage in self.contact_stages:
                    with obs.span("stage." + stage.name):
                        stage.run(self, seg, window)
            return self._window_report(window, segs)

    # window protocol pieces, shared with the fleet engine's batched
    # contact rounds so the drain/accounting rules live in ONE place

    def _window_is_noop(self) -> bool:
        return self._finalized and not self._pending

    @staticmethod
    def _drained_window_report() -> WindowReport:
        return WindowReport(budget_bytes=0.0, bytes_requested=0.0,
                            bytes_spent=0.0, tiles_downlinked=0, segments=0)

    def _open_window(self, budget_bytes, accrue: bool = True):
        """Pop the pending segments and accrue one window's byte budget
        (default: the pending segments' accumulated entitlement).

        ``accrue=False`` skips the byte-ledger accrual: the batched
        ContactPlan executor opens a whole round's windows first and
        accrues every lane in one vectorized
        :meth:`~repro.core.energy.FleetLedger.accrue_window_budgets` op
        (per-lane addition order unchanged — see that method)."""
        segs, self._pending = self._pending, []
        if budget_bytes is None:
            # re-queued segments (failed transmissions awaiting retry)
            # accrued their entitlement in their FIRST window; offering
            # it again would double-credit the byte budget
            budget_bytes = sum(s.byte_entitlement for s in segs
                               if not s.requeued)
        # denormal/negative budgets clamp to exact 0.0 before they can
        # accrue to the ledger or leak into the drain
        budget_bytes = clamp_budget_bytes(budget_bytes)
        window = ContactWindow(budget=budget_bytes,
                               remaining=budget_bytes)
        if accrue:
            self.bytes_ledger.budget += window.budget
        return segs, window

    @staticmethod
    def _window_report(window: ContactWindow, segs) -> WindowReport:
        return WindowReport(
            budget_bytes=window.budget,
            bytes_requested=sum(s.bytes_requested for s in segs),
            bytes_spent=sum(s.bytes_spent for s in segs),
            tiles_downlinked=sum(len(s.selection.downlink) for s in segs),
            segments=len(segs))

    # -- one-shot API -------------------------------------------------------

    def run(self, frames) -> PipelineResult:
        """Single ingest + one full-entitlement contact window — the
        ``run_pipeline`` compatibility semantics."""
        self.ingest(frames)
        self.contact_window()
        return self.result()

    def finalize(self) -> PipelineResult:
        """Flush pending segments through a zero-byte window (onboard
        results land, nothing transmits), then aggregate.

        Idempotent: repeated calls (and :meth:`contact_window` calls in
        between) are no-ops until a new :meth:`ingest` resumes the
        stream."""
        with obs.span("mission.finalize"):
            if self._pending:
                self.contact_window(0.0)
            self._finalized = True
            return self.result()

    def result(self) -> PipelineResult:
        """Aggregate over every segment that has been through a contact
        window. Call :meth:`finalize` to include un-windowed segments."""
        done = [s for s in self._segments if s.pred is not None]
        if done:
            pred = np.concatenate([s.pred for s in done])
            true = np.concatenate([s.true for s in done])
        else:
            pred = np.zeros(0, np.float64)
            true = np.zeros(0, np.float64)
        return PipelineResult(
            cmae=cmae(pred, true),
            total_true=float(true.sum()),
            total_pred=float(pred.sum()),
            bytes_downlinked=float(self.bytes_requested),
            bytes_budget=float(self.bytes_budget),
            tiles_processed_space=int(sum(s.n_processed for s in done)),
            tiles_downlinked=int(sum(len(s.selection.downlink) for s in done
                                     if s.selection is not None)),
            tiles_total=int(sum(s.n for s in done)),
            energy_spent_j=float(self.ledger.spent),
            energy_budget_j=float(self.ledger.budget_j),
            per_tile_pred=pred,
            per_tile_true=true,
        )

    @property
    def pending_segments(self) -> int:
        return len(self._pending)

    # -- shared helpers -----------------------------------------------------

    def _count(self, counter, tiles, idx):
        """Count ``tiles[idx]``: device gather + fixed-shape batches on
        the engine path, host slice + seed batching on the reference
        path."""
        params, cfg = counter
        if self.pcfg.use_engine:
            return count_tiles_batched(params, cfg, tiles, idx=idx,
                                       score_thresh=self.pcfg.score_thresh)
        return count_tiles_batched_ref(params, cfg, tiles[idx],
                                       score_thresh=self.pcfg.score_thresh)
