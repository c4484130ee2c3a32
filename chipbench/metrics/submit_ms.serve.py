"""Mean host milliseconds of one tenant submission: creating its
session and ``FederationServer.submit``."""
UNIT = "ms"
LAYER = "serve admission (core/fed/serve/server.py submit)"
MOVES = "tenant_job_ms_p95"


def read(win):
    sub = win.spans.get("submit", [])
    return 1000.0 * sum(sub) / len(sub) if sub else None
