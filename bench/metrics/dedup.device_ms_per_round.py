"""Device milliseconds per round of the dedup programs (k-means++ and
Lloyd core, final assignment; single and stacked over satellites)."""

PROGRAMS = ("_dedup_core_body", "_dedup_finalize_body", "_dedup_multi_core",
            "_dedup_finalize_multi")


def read(run):
    t, rounds = run["trace"], run["tally"]["rounds"]
    s = t.module_s(*PROGRAMS)
    return 1e3 * s / rounds if rounds and s > 0 else None
