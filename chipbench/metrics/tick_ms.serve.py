"""Mean host milliseconds of one ``FederationServer.tick()``: admission,
seating, the stacked dispatch of every group, its wait for the grid's
state, and retirement."""
UNIT = "ms"
LAYER = "serve tick (core/fed/serve/server.py tick, groups.py)"
MOVES = "tenant_job_ms_p95"


def read(win):
    ticks = win.spans.get("tick", [])
    return 1000.0 * sum(ticks) / len(ticks) if ticks else None
