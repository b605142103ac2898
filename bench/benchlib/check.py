"""The comparison that decides ``correct``.

Every session the window ran is held against the reference, stage by
stage. Each stage's answer is judged on the inputs it was given, and
those inputs are themselves answers judged one stage up, so every
stage is covered and a rounding choice upstream (a near tie, a count
that a bfloat16 operand tipped over the threshold) is not charged again
to every stage below it. The numbers, each worst over the window:

``moments_gap``   largest absolute gap of a tile's colour moment
                  (mean, stddev, skew cube root) from the reference's
                  capture of the same frames;
``rep_differ``    share of a round's tiles whose ROI verdict or dedup
                  representative differs from the reference's ROI filter
                  and dedup run on the program's moments (a member tied
                  for its cluster's representative may stand for it);
``count_miss``    share of the window's counted tiles (onboard and
                  ground) whose count the reference's counter, on the
                  reference's tiles, does not give for any score
                  threshold within ``BAND`` of the configuration's;
``conf_gap``      mean absolute gap of the onboard confidences from the
                  reference's, over tiles whose counts agree;
``select_differ`` share of a round's tiles whose onboard-count, downlink
                  or final-prediction verdict differs from the
                  reference's energy cap, selection and aggregation run
                  on the program's counts and confidences (exact);
``summary_gap``   largest relative gap of a session summary field from
                  the reference's ledgers and the program's predictions
                  (exact).
"""
from __future__ import annotations

import math

import numpy as np

from . import counters as C
from .reference import Ledger, truth

NUMBERS = ("moments_gap", "rep_differ", "count_miss", "conf_gap",
           "select_differ", "summary_gap")
SUMMARY_FIELDS = ("cmae", "total_true", "total_pred", "bytes_downlinked",
                  "bytes_budget", "tiles_processed_space", "tiles_downlinked",
                  "tiles_total", "energy_spent_j", "energy_budget_j")
# score band within which a count may differ by rounding: a few times
# the score noise that bfloat16 operands leave on the counters
BAND = 0.01


def compare(sessions, ref, passes):
    """``sessions``: the program's sessions, each ``{"keys": [pass key,
    ...], "rounds": [record, ...], "summary": {...}}``; ``ref``: a
    :class:`reference.Reference`; ``passes``: pass key -> frames.
    -> {number: worst value}."""
    worst = dict.fromkeys(NUMBERS, 0.0)

    def up(name, value):  # NaN reads as the worst there is
        value = float(value)
        worst[name] = max(worst[name],
                          value if math.isfinite(value) else math.inf)

    thresh = ref.model["score_thresh"]
    dedups = {}  # the window repeats its pool: judge each answer once
    counted = missed = 0
    conf_gaps = []
    for s in sessions:
        led = Ledger(ref.traffic, ref.gflops_space)
        preds, trues = [], []
        n_proc = n_down = 0
        for key, pr in zip(s["keys"], s["rounds"]):
            frames = passes[key]
            tiles, mom_ref = ref.capture(key, frames)
            n = len(tiles)
            mom = np.asarray(pr["moments"], np.float64)[:n]
            up("moments_gap", np.abs(mom - mom_ref).max())
            # ROI and dedup on the program's moments
            active, rep_of = pr["active"], pr["rep_of"]
            active_ref = ref.active(mom)
            seen = (key, mom.tobytes(), active.tobytes(), rep_of.tobytes())
            if seen not in dedups:
                dedups[seen] = ref.rep_of(mom, active, follow=rep_of)
            rep_ref = dedups[seen]
            up("rep_differ", ((active != active_ref)
                              | (rep_of != rep_ref)).mean())
            # the counters, tile by tile, on the reference's own tiles
            reps = np.where(pr["processed"] & (rep_of == np.arange(n)))[0]
            down = np.asarray(pr["downlink"], np.int64)
            for role, idx, counts, conf in (
                    ("space", reps, pr["counts_sp"], pr["conf"]),
                    ("ground", down, pr["counts_gd"], None)):
                for i, kept in zip(idx, ref.kept(key, tiles, role, idx)):
                    v = float(counts[i])
                    c = int(v) if math.isfinite(v) and v == int(v) else -1
                    counted += 1
                    missed += C.count_gap(c, kept, thresh) > BAND
                    c_ref, f_ref = C.count_and_conf(kept, thresh)
                    if conf is not None and c_ref == c:
                        conf_gaps.append(abs(float(conf[i]) - f_ref))
            # energy cap, selection and aggregation on the program's
            # onboard answers
            entitlement = led.grant(n, len(frames))
            if active.sum() > 4:
                led.aggregate(int(active.sum()))
            process = np.unique(rep_of[active])[:led.cap()]
            led.compute(len(process))
            processed = np.isin(rep_of, process) & active
            use_space, down_ref = ref.select(pr["conf"], processed, active,
                                             rep_of, entitlement)
            led.window(entitlement, len(down_ref) * ref.tile_bytes)
            pred_ref = ref.aggregate(pr["counts_sp"], pr["counts_gd"],
                                     use_space, down_ref, active, rep_of)
            tile = np.arange(n)
            up("select_differ", ((pr["processed"] != processed)
                                 | (np.isin(tile, down)
                                    != np.isin(tile, down_ref))
                                 | (pr["pred"] != pred_ref)).mean())
            preds.append(pr["pred"])
            trues.append(truth(frames, ref.traffic["tile_px"]))
            n_proc += len(process)
            n_down += len(down_ref)
        if s.get("summary") and preds:
            summary = ref.summary(preds, trues, led, n_proc, n_down)
            for f in SUMMARY_FIELDS:
                a, b = float(s["summary"][f]), float(summary[f])
                up("summary_gap", abs(a - b) / max(abs(b), 1e-12))
    if counted:
        up("count_miss", missed / counted)
    if conf_gaps:
        up("conf_gap", np.mean(conf_gaps))
    return worst


def verdict(numbers, limits):
    """-> (correct, [(name, value, limit)]); a missing or non-finite
    number fails."""
    rows = [(k, float(numbers.get(k, math.nan)), float(limits[k]))
            for k in NUMBERS]
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
