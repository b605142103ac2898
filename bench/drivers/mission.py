"""Driver of the ``mission`` entry: one satellite's ``Mission``.

A round is one pass: ``Mission.ingest(frames)`` then
``Mission.contact_window()``, with the results on the host when both
return. A session is ``session_rounds`` rounds (one orbit), then
``finalize()`` and a fresh ``Mission``; segments keep their device tiles
until then. Rounds cycle through a pool of passes made from the seed.

Each default stage runs inside a host span ``bench.stage.<name>``
(wrapped through ``Mission(ingest_stages=, contact_stages=)``), so that
the trace can say what the host was doing while the device was idle.
"""
from __future__ import annotations

import numpy as np


class _Spanned:
    """A program stage run inside a host span."""

    def __init__(self, stage, on_run=None):
        self.stage = stage
        self.name = stage.name
        self.span = "bench.stage." + stage.name
        self.on_run = on_run

    def run(self, mission, seg, window=None):
        from benchlib import trace
        if self.on_run is not None:
            self.on_run(seg)
        with trace.SPANS(self.span):
            if window is None:
                self.stage.run(mission, seg)
            else:
                self.stage.run(mission, seg, window)


def detector_config(spec):
    """The program's ``DetectorConfig`` from a counter entry of the
    benchmark's configuration file: each of its fields that the entry
    gives (lists as tuples), the dataclass's defaults for the others."""
    import dataclasses

    from repro.configs.base import DetectorConfig
    given = {f.name: spec[f.name] for f in dataclasses.fields(DetectorConfig)
             if f.name in spec}
    return DetectorConfig(**{k: tuple(v) if isinstance(v, list) else v
                             for k, v in given.items()})


def pipeline_config(model, traffic):
    """Every setting of the program's ``PipelineConfig`` that the cascade
    reads, stated from the configuration and the traffic file."""
    from repro.core.energy import DeviceProfile
    from repro.core.pipeline import PipelineConfig
    b, e = traffic["budgets"], traffic["energy_model"]
    return PipelineConfig(
        method=traffic["policy"], tile_size=traffic["tile_px"],
        conf_p=traffic["conf_p"], conf_q=traffic["conf_q"],
        policy=traffic["fill_order"], bandwidth_mbps=b["bandwidth_mbps"],
        contact_s=b["contact_s"], contacts_per_day=b["contacts_per_day"],
        energy_budget_j=b["energy_budget_j"],
        hardware=DeviceProfile(e["device"], e["device_power_w"],
                               e["device_gflops"]),
        use_dedup=True, k_clusters=None, use_roi=True,
        roi_std_thresh=traffic["roi_std_thresh"],
        score_thresh=model["score_thresh"],
        tiles_per_day=b["tiles_per_day"], real_tile_px=b["real_tile_px"],
        seed=traffic["dedup"]["seed"], use_engine=True)


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.model = ctx.config
        self.traffic = ctx.traffic
        self.session_rounds = int(self.traffic["session_rounds"])
        self.mission = None
        self.keys = []
        self.segs = []
        self.sessions = []
        self.tally = self._fresh_tally()

    # -- set-up ---------------------------------------------------------

    def setup(self):
        """Scenes on the host, then both counters' weights on the device
        in one jitted call from the seed (heads calibrated on every 8th
        tile of the pool's first pass)."""
        import jax
        import jax.numpy as jnp
        from benchlib import counters, reference, scenes
        sc = self.traffic["scenes"]
        self.pool = scenes.pass_pool(
            np.random.default_rng(self.ctx.scene_seed), scenes.spec_from(sc),
            int(self.traffic["pool_passes"]), int(sc["per_pass"]),
            int(sc["revisits"]))
        specs = {r: self.model["counters"][r] for r in ("space", "ground")}
        calib = reference.tile_frames(self.pool[0],
                                      self.traffic["tile_px"])[::8]

        def make(key, tiles):
            out = {}
            for r, kr in zip(specs, jax.random.split(key, len(specs))):
                p = counters.init_params(kr, specs[r])
                x = counters.resize(tiles, specs[r]["input_size"])
                out[r] = counters.calibrate_head(p, specs[r], x)
            return out

        params = jax.block_until_ready(
            jax.jit(make)(self.ctx.weights_key, jnp.asarray(calib)))
        self.counters = {r: (params[r], specs[r]) for r in specs}
        self.det = {r: detector_config(specs[r]) for r in specs}
        self.pcfg = pipeline_config(self.model, self.traffic)
        self.gflops = {r: counters.forward_gflops(specs[r]) for r in specs}

    def warmup(self):
        """One cycle of the pool, in sessions as the window runs them:
        every batch tier and frame bucket the window uses compiles here."""
        for r in range(len(self.pool)):
            self.round(r)
            self.after_round(r)
        self.close()
        self.sessions.clear()
        self.tally = self._fresh_tally()

    @staticmethod
    def _fresh_tally():
        t = dict.fromkeys(("rounds", "tiles", "space_tiles", "ground_tiles",
                           "frames"), 0)
        t["dedup_rows"] = []  # active tiles of each pass that was deduped
        return t

    def trace_rounds(self) -> int:
        """Two cycles of the pool: two whole sessions."""
        return 2 * len(self.pool)

    # -- the window -----------------------------------------------------

    def _open(self):
        from repro.core.mission import (Mission, default_contact_stages,
                                        default_ingest_stages)
        ingest = [_Spanned(s) for s in default_ingest_stages()]
        ingest[0].on_run = self.segs.append
        self.mission = Mission(
            (self.counters["space"][0], self.det["space"]),
            (self.counters["ground"][0], self.det["ground"]), self.pcfg,
            energy_cfgs=(self.det["space"], self.det["ground"]),
            ingest_stages=ingest,
            contact_stages=[_Spanned(s) for s in default_contact_stages()])

    def round(self, r: int) -> int:
        """One pass; -> real tiles captured."""
        if self.mission is None:
            self._open()
        k = r % len(self.pool)
        frames = self.pool[k]
        rep = self.mission.ingest(frames)
        win = self.mission.contact_window()
        self.keys.append(k)
        t = self.tally
        t["rounds"] += 1
        t["frames"] += len(frames)
        t["tiles"] += rep.n_tiles
        t["space_tiles"] += rep.tiles_processed_space
        t["ground_tiles"] += win.tiles_downlinked
        n_active = int(self.segs[-1].active.sum())
        if n_active > 4:
            t["dedup_rows"].append(n_active)
        return rep.n_tiles

    def after_round(self, r: int):
        if len(self.keys) >= self.session_rounds:
            self._finalize()

    def close(self):
        if self.mission is not None and self.keys:
            self._finalize()

    def _finalize(self):
        summary = self.mission.finalize().summary()
        rounds = [dict(moments=s.prep.moments, active=s.active,
                       rep_of=s.rep_of, processed=s.processed,
                       counts_sp=s.counts_sp, conf=s.conf,
                       downlink=s.selection.downlink, counts_gd=s.counts_gd,
                       pred=s.pred) for s in self.segs]
        self.sessions.append(dict(keys=list(self.keys), rounds=rounds,
                                  summary=summary))
        self.mission, self.keys, self.segs = None, [], []

    # -- after the window -----------------------------------------------

    def flops(self) -> float:
        """Counter operations of the real tiles counted so far."""
        t = self.tally
        return 1e9 * (t["space_tiles"] * self.gflops["space"]
                      + t["ground_tiles"] * self.gflops["ground"])

    def outputs(self):
        """(sessions, pass index -> frames) for the comparison; device
        arrays brought to the host."""
        for s in self.sessions:
            for r in s["rounds"]:
                r["moments"] = np.asarray(r["moments"])
        return self.sessions, dict(enumerate(self.pool))

    def drop_program_state(self):
        self.mission, self.segs = None, []
