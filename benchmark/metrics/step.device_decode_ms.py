"""The device's decode-only step, on the device's own clock: as
``step.device_prefill_ms`` (whose file has the join) for the executions
whose launch carried no prefill chunk."""
from harness import spec

device_steps = spec._load_module("metrics", "step.device_prefill_ms",
                                 "reader for metric").device_steps


def read(ctx):
    ms = [d for chunks, d in device_steps(ctx) if chunks == 0]
    return sum(ms) / len(ms) if ms else None
