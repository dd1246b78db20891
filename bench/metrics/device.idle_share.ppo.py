"""Share of the traced stage-3 iterations, in percent, in which no
operation ran on the device (1 - busy union / traced window)."""


def read(rec):
    t = rec.get("trace")
    if t is None or t.window_s <= 0 or t.n_devices == 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
