"""Median time a request waited before any of its prompt was computed:
from ``request.queued`` to the start of the ``engine.device_launch`` of
the step that carried its first chunk (``request.prefill_chunk`` names
that step), over requests queued inside the window.  Queueing for a slot
and for the prefill budget, nothing of prefill itself."""
from harness import scopes, stats


def read(ctx):
    waits = scopes.prefill_waits_ns(ctx["spans"], ctx["t_open"],
                                    ctx["t_close"])
    if not waits:
        return None
    return stats.percentile([w / 1e6 for w in waits], 50)
