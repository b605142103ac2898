"""Device milliseconds per round of the counting programs (detector
forward, decode and NMS; onboard and ground, single and stacked)."""

PROGRAMS = ("count_tiles", "_count_tiles_chunks")


def read(run):
    t, rounds = run["trace"], run["tally"]["rounds"]
    s = t.module_s(*PROGRAMS)
    return 1e3 * s / rounds if rounds and s > 0 else None
