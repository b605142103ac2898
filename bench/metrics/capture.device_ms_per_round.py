"""Device milliseconds per round of the capture programs (the fused
tile / resize / colour-moments program, single and stacked)."""

PROGRAMS = ("_frame_program_body", "_frame_program_multi")


def read(run):
    t, rounds = run["trace"], run["tally"]["rounds"]
    s = t.module_s(*PROGRAMS)
    return 1e3 * s / rounds if rounds and s > 0 else None
