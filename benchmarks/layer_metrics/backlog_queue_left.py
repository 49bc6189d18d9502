"""Requests still queued at the window's close over the requests the backlog held, in percent: the room the cell has left. Under about 5% the next gain runs the cell dry (`backlog_never_dry`): deepen its backlog first."""

from benchmarks.harness.readers import counter


def read(ctx):
    queued = counter(ctx, "queued_at_close")
    held = counter(ctx, "requests_submitted")
    if ctx.traffic.get("arrival") != "backlog" or queued is None or not held:
        return None
    return 100.0 * queued / held
