"""Mean host milliseconds of one ``session.step()`` call, the host's
enqueue time of a round (harness clock between the session's
callbacks)."""
UNIT = "ms"
LAYER = "session and scheduler (core/fed/api/session.py, scheduler.py)"
MOVES = "rounds_per_s"


def read(win):
    steps = win.spans.get("step", [])
    return 1000.0 * sum(steps) / len(steps) if steps else None
