"""Rounds the window completed (a metric that exists only in test data)."""
UNIT = "rounds"
LAYER = "session and scheduler (core/fed/api/session.py, scheduler.py)"
MOVES = "rounds_per_s"


def read(win):
    return float(win.rounds) if win.rounds else None
