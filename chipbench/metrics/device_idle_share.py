"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""
UNIT = "%"
LAYER = "device (TPU v5e)"
MOVES = "rounds_per_s"


def read(win):
    if win.trace is None or win.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - win.trace.busy_s / win.trace.window_s)
