"""Device-idle milliseconds per round of the traced window: what the
stage graph's host work (and its waits) leaves the chip idle. The
breakdown's ``idle_gaps`` names the host span open in each gap."""


def read(run):
    t, rounds = run["trace"], run["tally"]["rounds"]
    if not rounds or t.n_devices == 0:
        return None
    return 1e3 * (t.window_s - t.busy_s) / rounds
