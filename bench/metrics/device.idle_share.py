"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (1 - union of device-op intervals /
window)."""


def read(run):
    t = run["trace"]
    if t.window_s <= 0 or t.n_devices == 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
