"""Plain reference of one satellite's TargetFuse session.

It follows the semantics of the cascade as the configuration and the
traffic state them, and imports nothing of the program:

    ingest:  tile -> bilinear resize -> colour moments -> ROI filter ->
             k-means++ / Lloyd dedup -> energy-capped onboard count
    contact: two-threshold selection (dynamic confidence fill of the
             window's bytes) -> downlink -> ground recount -> aggregate

The pieces are used one by one by ``check.py``; :meth:`Reference.session`
chains them, which is how the control runs in the program's place.
Device work (resize, moments, the counters) is plain ``jax.numpy`` at
``HIGHEST`` precision; decode, NMS, dedup, the ledgers and the selection
run in numpy on the host.

``mode`` is the precision the configuration states, or a control's:
``default`` (the TPU's default matmul precision: bfloat16 operands,
float32 sums; capture in float32), ``highest`` (float32 throughout, the
CPU's default), or one step below what the configuration states:
``control`` in every stage (capture and dedup features in bfloat16, the
counters' convs in int8), ``control-capture`` in capture and dedup
alone, ``control-count`` in the counters alone.
"""
from __future__ import annotations

from functools import partial

import numpy as np

from . import counters as C

BATCH = 16  # tiles per reference counter call (padded)
# the controls, each one precision step below the configuration in the
# stages it names (see ``mode`` above)
CONTROLS = ("control", "control-capture", "control-count")
# squared-distance ties in dedup, relative to the largest |x|^2: float32
# rounding of |x|^2 - 2 x.c + |c|^2 is a few 1e-7 of it
TIE = 1e-5


def tile_frames(frames, tile: int) -> np.ndarray:
    """Frames (H, W, 3) -> (N, tile, tile, 3), row-major within each frame,
    frames in order; edges zero-padded to whole tiles."""
    out = []
    for img, _, _ in frames:
        img = np.asarray(img, np.float32)
        h, w, c = img.shape
        img = np.pad(img, ((0, -h % tile), (0, -w % tile), (0, 0)))
        gh, gw = img.shape[0] // tile, img.shape[1] // tile
        out.append(img.reshape(gh, tile, gw, tile, c).transpose(0, 2, 1, 3, 4)
                   .reshape(gh * gw, tile, tile, c))
    return np.concatenate(out)


def truth(frames, tile: int) -> np.ndarray:
    """Objects per tile, each in the tile that holds its centre."""
    out = []
    for img, boxes, _ in frames:
        g = -(-np.shape(img)[0] // tile)
        counts = np.zeros((g, g), np.int64)
        for x1, y1, x2, y2 in boxes:
            cx, cy = (x1 + x2) / 2, (y1 + y2) / 2
            counts[min(int(cy // tile), g - 1),
                   min(int(cx // tile), g - 1)] += 1
        out.append(counts.reshape(-1))
    return np.concatenate(out).astype(np.float64)


class Ledger:
    """One satellite's energy and byte accounts, in the order the
    configuration states them (float64)."""

    def __init__(self, traffic, gflops_space: float):
        self.b, self.e = traffic["budgets"], traffic["energy_model"]
        self.j_per_gflop = self.e["device_power_w"] / self.e["device_gflops"]
        self.gflops = gflops_space
        self.budget = self.cap_j = self.com = self.agg = self.down = 0.0
        self.bytes_budget = self.bytes_requested = 0.0

    @property
    def spent(self):
        return self.cap_j + self.com + self.agg + self.down

    def grant(self, n_tiles: int, n_frames: int) -> float:
        """A pass's day-fraction grant; -> its byte entitlement."""
        b = self.b
        frac = n_tiles / b["tiles_per_day"]
        self.budget += b["energy_budget_j"] * frac
        self.cap_j += n_frames * self.e["capture_j_per_frame"]
        return (b["bandwidth_mbps"] * 1e6 / 8.0 * b["contact_s"]
                * b["contacts_per_day"] * frac)

    def cap(self) -> int:
        remaining = max(self.budget - self.spent, 0.0)
        return int(remaining * self.e["energy_margin"]
                   / (self.gflops * self.j_per_gflop))

    def aggregate(self, n_ops: int):
        self.agg += n_ops * self.e["aggregate_j_per_op"]

    def compute(self, n_tiles: int):
        self.com += n_tiles * self.gflops * self.j_per_gflop

    def window(self, entitlement: float, requested: float):
        self.bytes_budget += entitlement
        self.bytes_requested += requested
        spend = min(requested, entitlement)
        self.down += (spend * 8.0 / (self.b["bandwidth_mbps"] * 1e6)
                      * self.e["radio_power_w"])


class Reference:
    """``counters``: role -> (params, spec) for ``space`` and ``ground``;
    ``model``: the configuration; ``traffic``: the mix."""

    def __init__(self, counters, model, traffic, mode: str = "default"):
        import jax
        import jax.numpy as jnp

        self.counters = counters
        self.model = model
        self.traffic = traffic
        self.mode = mode
        self._kept = {}
        self._captured = {}
        low_capture = mode in ("control", "control-capture")
        self.low_capture = low_capture
        dt = jnp.bfloat16 if low_capture else jnp.float32
        conv_mode = ("int8" if mode in ("control", "control-count") else
                     "default" if mode == "control-capture" else mode)

        def moments(t):
            x = C.resize(t, counters["space"][1]["input_size"], dt)
            mu = jnp.mean(x, axis=(1, 2), keepdims=True)
            d = x - mu
            var = jnp.mean(d * d, axis=(1, 2))
            m3 = jnp.mean(d * d * d, axis=(1, 2))
            return jnp.concatenate(
                [mu[:, 0, 0], jnp.sqrt(var), jnp.cbrt(m3)], -1
            ).astype(jnp.float32)

        def raw(params, t, *, role):
            spec = counters[role][1]
            x = C.resize(t, spec["input_size"], dt).astype(jnp.float32)
            return C.reference_forward(params, spec, x, conv_mode)

        self._moments = jax.jit(moments)
        self._raw = {r: jax.jit(partial(raw, role=r)) for r in counters}
        self.gflops_space = C.forward_gflops(counters["space"][1])
        b = traffic["budgets"]
        self.tile_bytes = float(b["real_tile_px"] ** 2 * 3)

    # -- capture and the counters -----------------------------------------

    def capture(self, key, frames):
        """-> (tiles at the capture size, host; moments (n, 9) float64),
        cached per pass key."""
        import jax.numpy as jnp
        if key not in self._captured:
            tiles = tile_frames(frames, self.traffic["tile_px"])
            m = [np.asarray(self._moments(jnp.asarray(tiles[i:i + 64])))
                 for i in range(0, len(tiles), 64)]
            self._captured[key] = (tiles,
                                   np.concatenate(m).astype(np.float64))
        return self._captured[key]

    def kept(self, key, tiles, role: str, idx):
        """Kept NMS scores (descending) of ``tiles[idx]`` under counter
        ``role``; cached per (key, role, tile). Decode is per tile, so the
        padded rows are decoded and left out."""
        import jax
        import jax.numpy as jnp
        params, spec = self.counters[role]
        todo = sorted({int(i) for i in idx
                       if (key, role, int(i)) not in self._kept})
        for s in range(0, len(todo), BATCH):
            part = todo[s:s + BATCH]
            batch = np.zeros((BATCH, *tiles.shape[1:]), np.float32)
            batch[:len(part)] = tiles[part]
            raw = jax.tree.map(np.asarray,
                               self._raw[role](params, jnp.asarray(batch)))
            boxes, scores = C.decode(raw, spec)
            for j, i in enumerate(part):
                self._kept[(key, role, i)] = C.kept_scores(
                    boxes[j], scores[j], self.model["nms_iou"])
        return [self._kept[(key, role, int(i))] for i in idx]

    def counts(self, key, tiles, role, idx):
        t = self.model["score_thresh"]
        cc = [C.count_and_conf(k, t) for k in self.kept(key, tiles, role, idx)]
        return (np.array([c for c, _ in cc], np.float64),
                np.array([f for _, f in cc], np.float64))

    # -- ROI and dedup ----------------------------------------------------

    def active(self, moments) -> np.ndarray:
        """ROI: the mean of a tile's three channel stddevs (float32) over
        the traffic's threshold."""
        f = np.float32
        sd = np.asarray(moments, f)[:, 3:6]
        return (sd[:, 0] + sd[:, 1] + sd[:, 2]) / f(3) > f(
            self.traffic["roi_std_thresh"])

    def dedup(self, moments, k: int):
        """k-means++ (first centre drawn by ``jax.random.randint`` from
        the traffic's dedup seed, then greedy farthest points) and Lloyd
        iterations over the centred, globally scaled moments; each
        cluster's representative is its lowest-index member nearest the
        centre. Float32 as the configuration states it, with squared
        distances as |x|^2 - 2 x.c + |c|^2 (clamped at 0); the centroid
        sums are a matmul at the default precision, bfloat16 operands on
        the TPU. Members whose distance lies within ``TIE`` of the
        nearest are tied: a cluster of two has both members at the same
        distance from its mean, and rounding alone picks one.
        -> (assign, representative of each cluster, tied (n,) bool)."""
        import jax
        f = np.float32
        m = np.asarray(moments, f)
        if self.low_capture:
            m = _bf16(m).astype(f)
        n, d = m.shape
        mu = m.sum(0, keepdims=True, dtype=f) / f(n)
        gmu = m.sum(dtype=f) / f(n * d)
        scale = np.sqrt(((m - gmu) ** 2).sum(dtype=f) / f(n * d)) + f(1e-6)
        x = ((m - mu) / scale).astype(f)
        x2 = (x * x).sum(-1, dtype=f)

        def d2(c):
            c = np.asarray(c, f).reshape(-1, d)
            cross = (x.astype(np.float64) @ c.T.astype(np.float64)).astype(f)
            out = np.maximum(x2[:, None] - f(2) * cross
                             + (c * c).sum(-1, dtype=f)[None, :], f(0))
            # the control keeps its distances in bfloat16
            return _bf16(out).astype(f) if self.low_capture else out

        first = int(jax.random.randint(
            jax.random.PRNGKey(self.traffic["dedup"]["seed"]), (), 0, n))
        cents = [x[first]]
        dist = d2(x[first])[:, 0]
        for _ in range(1, k):
            nxt = int(np.argmax(dist))
            cents.append(x[nxt])
            dist = np.minimum(dist, d2(x[nxt])[:, 0])
        cent = np.stack(cents)
        xs = _bf16(x).astype(f) if self.mode != "highest" else x
        for _ in range(self.traffic["dedup"]["iters"]):
            assign = np.argmin(d2(cent), -1)
            one = np.zeros((n, k), f)
            one[np.arange(n), assign] = 1
            cnt = one.sum(0)
            tot = (one.T.astype(np.float64) @ xs.astype(np.float64)).astype(f)
            cent = np.where(cnt[:, None] > 0,
                            tot / np.maximum(cnt, 1)[:, None], cent).astype(f)
        dd = d2(cent)
        assign = np.argmin(dd, -1)
        near = dd[np.arange(n), assign]
        best = np.full(k, np.inf, f)
        np.minimum.at(best, assign, near)
        tied = near <= best[assign] + f(TIE * max(float(x2.max()), 1.0))
        rep = np.full(k, n, np.int64)
        np.minimum.at(rep, assign,
                      np.where(near <= best[assign], np.arange(n), n))
        return assign, rep, tied

    def rep_of(self, moments, active, follow=None) -> np.ndarray:
        """Each tile's dedup representative (itself where dedup does not
        run: 4 active tiles or fewer). ``follow``: another answer's
        representatives; where one of them is tied for its cluster's
        representative it is taken (either is right, rounding chose)."""
        n = len(active)
        rep_of = np.arange(n)
        idx = np.where(active)[0]
        if len(idx) <= 4:
            return rep_of
        assign, rep, tied = self.dedup(np.asarray(moments)[idx],
                                       max(2, len(idx) // 2))
        rep_of[idx] = idx[rep[assign]]
        if follow is not None:
            for j in np.unique(assign):
                members = idx[assign == j]
                pick = follow[members[0]]
                if np.all(follow[members] == pick) and np.any(
                        (members == pick) & tied[assign == j]):
                    rep_of[members] = pick
        return rep_of

    # -- selection --------------------------------------------------------

    def select(self, conf, processed, active, rep_of, budget):
        """Two-threshold selection over the processed representatives:
        confidence under ``conf_p`` discards, over ``conf_q`` accepts the
        onboard count, and the middle fills the window's bytes in
        descending confidence (leftovers keep their onboard count); bytes
        left over carry unprocessed representatives, lowest index first.
        Comparisons in float32, as the configuration states them.
        -> (tiles that take the onboard count, tiles downlinked)."""
        tr, tb = self.traffic, self.tile_bytes
        n = len(conf)
        rep_self = rep_of == np.arange(n)
        reps = np.where(processed & rep_self)[0]
        c = conf[reps].astype(np.float32)
        discard = c < np.float32(tr["conf_p"])
        high = c > np.float32(tr["conf_q"])
        middle = ~discard & ~high
        order = np.argsort(np.where(middle, -c, np.inf), kind="stable")
        sizes = np.where(middle, np.float32(tb), np.float32(0))[order]
        fits = (np.cumsum(sizes, dtype=np.float32) <= np.float32(budget)) \
            & middle[order]
        down_m = np.zeros(len(reps), bool)
        down_m[order] = fits
        space_m = high | (middle & ~down_m)
        down_reps = reps[down_m]
        unproc = np.where(active & rep_self & ~processed)[0]
        extra = int(max(budget - len(down_reps) * tb, 0.0) // tb)
        down = np.concatenate([down_reps, unproc[:extra]]).astype(np.int64)
        rep_space = np.zeros(n, bool)
        rep_space[reps[space_m]] = True
        return rep_space[rep_of] & processed, down

    @staticmethod
    def aggregate(counts_sp, counts_gd, use_space, down, active, rep_of):
        n = len(rep_of)
        use_ground = np.zeros(n, bool)
        use_ground[down] = True
        use_ground = use_ground[rep_of] & active
        use_space = use_space & ~use_ground
        pred = np.zeros(n)
        pred[use_space] = counts_sp[use_space]
        pred[use_ground] = counts_gd[use_ground]
        return pred

    @staticmethod
    def summary(preds, trues, ledger, n_processed, n_down) -> dict:
        pred, true = np.concatenate(preds), np.concatenate(trues)
        return dict(
            cmae=float(np.abs(pred - true).sum() / max(true.sum(), 1e-9)),
            total_true=float(true.sum()), total_pred=float(pred.sum()),
            bytes_downlinked=float(ledger.bytes_requested),
            bytes_budget=float(ledger.bytes_budget),
            tiles_processed_space=int(n_processed),
            tiles_downlinked=int(n_down), tiles_total=int(len(pred)),
            energy_spent_j=float(ledger.spent),
            energy_budget_j=float(ledger.budget))

    # -- the whole chain (the control in the program's place) -------------

    def session(self, passes, keys):
        """Run one session over ``passes`` (frame lists; ``keys`` name
        them for the caches) through every stage, as the program would.
        -> (rounds, summary), the rounds in the form the drivers record
        the program's."""
        led = Ledger(self.traffic, self.gflops_space)
        rounds, preds, trues = [], [], []
        n_proc = n_down = 0
        for frames, key in zip(passes, keys):
            tiles, mom = self.capture(key, frames)
            n = len(tiles)
            entitlement = led.grant(n, len(frames))
            active = self.active(mom)
            rep_of = self.rep_of(mom, active)
            if active.sum() > 4:
                led.aggregate(int(active.sum()))
            process = np.unique(rep_of[active])[:led.cap()]
            led.compute(len(process))
            c_raw, f_raw = np.zeros(n), np.full(n, -1.0)
            if len(process):
                c, f = self.counts(key, tiles, "space", process)
                c_raw[process], f_raw[process] = c, np.float32(f)
            processed = np.isin(rep_of, process) & active
            counts_sp, conf = c_raw[rep_of], f_raw[rep_of]
            use_space, down = self.select(conf, processed, active, rep_of,
                                          entitlement)
            led.window(entitlement, len(down) * self.tile_bytes)
            g_raw = np.zeros(n)
            if len(down):
                g_raw[down] = self.counts(key, tiles, "ground", down)[0]
            counts_gd = g_raw[rep_of]
            pred = self.aggregate(counts_sp, counts_gd, use_space, down,
                                  active, rep_of)
            rounds.append(dict(moments=mom, active=active, rep_of=rep_of,
                               processed=processed, counts_sp=counts_sp,
                               conf=conf, downlink=down, counts_gd=counts_gd,
                               pred=pred))
            preds.append(pred)
            trues.append(truth(frames, self.traffic["tile_px"]))
            n_proc += len(process)
            n_down += len(down)
        return rounds, self.summary(preds, trues, led, n_proc, n_down)


def _bf16(a):
    import ml_dtypes
    return np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.float64)
