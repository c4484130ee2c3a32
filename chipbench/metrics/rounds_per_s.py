"""Federation rounds completed in the window per second of it (tenant
rounds summed over tenants when serving). A round counts once its state
is ready on the device."""
UNIT = "rounds/s"


def read(win):
    return win.rounds / win.seconds if win.rounds else None
