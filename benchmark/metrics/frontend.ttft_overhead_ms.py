"""Client's median time to first token less the engine's own median
from queueing a request to its first token (Tracer instants
``request.queued`` and ``request.first_token``), over the window's dealt
requests: what HTTP, the runner's queues and SSE add."""
from harness import spans as S, stats


def read(ctx):
    t0, t1 = ctx["t_open"], ctx["t_close"]
    queued = {s["args"]["rid"]: s["ts"]
              for s in S.named(ctx["spans"], "request.queued")}
    inside = [s["ts"] - queued[s["args"]["rid"]]
              for s in S.named(ctx["spans"], "request.first_token")
              if t0 <= s["ts"] < t1 and s["args"]["rid"] in queued]
    # primers are left out of the client's median; leave out as many of
    # the engine's shortest (primers prefill 32 tokens: the shortest)
    client = ctx["view"]["ttfts_ms"]
    if not inside or not client:
        return None
    inside.sort()
    engine = inside[max(0, len(inside) - len(client)):]
    return stats.percentile(client, 50) \
        - stats.percentile([x / 1e6 for x in engine], 50)
