"""Median time to first token on the client's clock, in the cells where
it is not an end-to-end metric: there four of five steps carry a prefill
chunk, requests queue for the prefill budget, and this is queueing time
that swings from run to run (PERF.md section 2)."""


def read(ctx):
    return ctx["e2e"].get("ttft_p50_ms")
