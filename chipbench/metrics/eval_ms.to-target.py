"""Mean host milliseconds of one evaluation of train and test fidelity,
the host's wait for its result included."""
UNIT = "ms"
LAYER = "evaluation (core/fed/api/substrate.py evaluate, federated.evaluate)"
MOVES = "time_to_fidelity_s"


def read(win):
    ev = win.spans.get("eval", [])
    return 1000.0 * sum(ev) / len(ev) if ev else None
