"""The device's step, on the device's own clock: mean duration of the
executions of the step programs (the ``XLA Modules`` line of the
profile) that ran whole inside the traced window and whose launch
carried a prefill chunk.  ``step.prefill_ms`` times the same launches
from the host, launch to result SEEN, and so holds part of the turn.

An execution belongs to the last ``engine.launch`` annotated at or
before it began (launch k + 1 is annotated while launch k runs, launch
k + 2 only after k was seen complete, which is after k + 1 began), and
that annotation's step id finds the launch's ``engine.device_launch``
(``chunks``).  One walk of the sorted executions beside the sorted
annotations.  Nothing to read without a profile, or from a program
that names neither its programs nor its launches."""
from harness import scopes


def device_steps(ctx) -> list:
    """(chunks, milliseconds) of each such execution; worked out once
    for the two readers and kept in ``ctx``."""
    tr, programs = ctx.get("trace"), ctx.get("program_scopes")
    if tr is None or not programs:
        return []
    if "_device_steps" not in ctx:
        _ops, modules, launches = scopes._read(scopes.trace_dir(),
                                               tr["plane"])
        args = scopes.launch_args(ctx["spans"])
        w0, w1 = tr["window"]
        out, li = [], -1
        for m in modules:
            while li + 1 < len(launches) \
                    and launches[li + 1]["start_ns"] <= m["start_ns"]:
                li += 1
            if li < 0 or m["program"] not in programs \
                    or m["start_ns"] < w0 \
                    or m["start_ns"] + m["dur_ns"] > w1:
                continue
            a = args.get(launches[li]["step"])
            if a is not None:
                out.append((int(a.get("chunks", 0)), m["dur_ns"] / 1e6))
        ctx["_device_steps"] = out
    return ctx["_device_steps"]


def read(ctx):
    ms = [d for chunks, d in device_steps(ctx) if chunks > 0]
    return sum(ms) / len(ms) if ms else None
