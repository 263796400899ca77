"""Finding model shared by every graft-lint front end.

Both front ends — the jaxpr analyzer (jaxpr_passes.py) and the Python
AST linter (ast_rules.py) — report through one ``Finding`` record so the
CLI, the baseline file, the pytest plugin, and ``enforce`` never care
which analysis produced a result.  The shape mirrors what every mature
linter converges on (rule id, severity, location, message) plus a
``trail``: the jaxpr passes attach the equation's user-source frames so
a per-equation dtype promotion points at the line of model code that
wrote it, not at a lowering internal.

Baselines: a committed JSON file of accepted-finding fingerprints (rule
+ file + function + message, intentionally NOT the line number, so pure
line drift never resurrects an accepted finding).  ``filter_baseline``
subtracts it; the CLI's exit code and the strict import-time enforce
both look only at what survives.
"""
from __future__ import annotations

import hashlib
import json
import os
from dataclasses import dataclass, field

__all__ = [
    "ERROR", "WARNING", "INFO", "SEVERITIES", "Location", "Finding",
    "RULES", "rule_severity", "load_baseline", "save_baseline",
    "filter_baseline", "findings_to_json", "format_text",
]

ERROR = "ERROR"
WARNING = "WARNING"
INFO = "INFO"
SEVERITIES = (ERROR, WARNING, INFO)          # most severe first


# ---------------------------------------------------------------------------
# rule catalog: every rule either front end can emit, with its default
# severity and the hazard it guards.  tests/test_graftlint.py asserts each
# catalog rule is covered by at least one firing fixture.
# ---------------------------------------------------------------------------

RULES = {
    # jaxpr front end
    "undonated-buffer": (ERROR, "jaxpr", (
        "a large input buffer (params/KV-cache scale) matches an output's "
        "shape+dtype but is not in donate_argnums — every call copies it "
        "instead of updating in place")),
    "host-callback": (ERROR, "jaxpr", (
        "a callback primitive (pure_callback/io_callback/debug_callback) "
        "inside a compiled program — a device->host round-trip on every "
        "execution")),
    "dtype-promotion": (WARNING, "jaxpr", (
        "an f32/f64 upcast of a low-precision value inside a "
        "declared-bf16/f16 program — silent promotions quietly double "
        "bandwidth; intentional ones (softmax, logits) belong in the "
        "baseline")),
    "dead-code": (WARNING, "jaxpr", (
        "an equation whose outputs never reach a program output — wasted "
        "FLOPs XLA may or may not DCE depending on effects")),
    "dead-input": (WARNING, "jaxpr", (
        "a program input no equation and no output ever reads — a wasted "
        "transfer and a recompile key that does nothing")),
    "passthrough-output": (INFO, "jaxpr", (
        "an output that is an input forwarded untouched — usually a "
        "threading convenience; flags a buffer that could be dropped from "
        "the signature")),
    # AST front end
    "numpy-in-jit": (ERROR, "ast", (
        "a numpy call inside a jit-compiled body — it either escapes the "
        "trace (host sync) or fails on tracers at runtime")),
    "host-sync-in-jit": (ERROR, "ast", (
        ".item()/.tolist()/.numpy()/float()/int()/bool() on a traced value "
        "inside a compiled body — forces a device->host transfer or a "
        "ConcretizationTypeError")),
    "tracer-branch": (ERROR, "ast", (
        "`if`/`while` on a parameter of a jit-compiled function — Python "
        "control flow on a tracer recompiles per value or raises; use "
        "lax.cond/select")),
    "mutable-default-arg": (WARNING, "ast", (
        "a mutable default argument ([]/{}); inside a compiled path it is "
        "also a hidden retrace key (severity ERROR there)")),
    "unkeyed-jit": (ERROR, "ast", (
        "jax.jit created per call (immediately invoked, or built inside a "
        "loop) — a fresh cache entry every time, i.e. recompile hazard; "
        "hoist it or key it in a cache dict")),
    "attention-program-budget": (ERROR, "ast", (
        "an attention-bearing compiled program (jax.jit or pallas_call) "
        "in the inference tier beyond the budget — one program kind per "
        "attention kind the module declares for its models' layers "
        "(ATTENTION_KINDS; ONE, the ragged step, without a declaration); "
        "phase-special attention kernels reintroduce bucket "
        "fragmentation and recompiles")),
    "quantized-kv-float32-page": (WARNING, "ast", (
        "a float32 allocation bound to a KV-page-like name inside an "
        "inference-tier kv_dtype == \"int8\" branch — quantized engines "
        "store int8 pages (with f32 scale rows in a parallel pool); a "
        "float32 page pool silently forfeits the ~4x HBM headroom the "
        "format exists for")),
    "f32-weight-matmul-in-quantized-engine": (WARNING, "ast", (
        "a dense matmul against a raw weight-pool entry (h @ p[\"wq\"], "
        "jnp.einsum with params[...]) inside an inference-tier "
        "weight_dtype != \"float32\" branch — quantized engines hold "
        "int8/int4 pools (name_q) with scale rows (name_s) and route "
        "every projection/MLP/head contraction through the fused "
        "dequant-matmul helper; a dense matmul there either KeyErrors "
        "on the quantized pool or silently streams f32 weights, "
        "forfeiting the 4x/8x weight-byte win")),
    "swallowed-exception": (ERROR, "ast", (
        "a bare/broad `except` that only passes (or logs and continues) "
        "inside an inference-tier step/release/abort/recover path — the "
        "supervised-recovery watchdog and quarantine logic depend on "
        "failures surfacing; an eaten exception turns a crashed step "
        "into a silent hang or a leaked sequence")),
    "untuned-pallas-launch": (WARNING, "ast", (
        "a pl.pallas_call in ops/pallas whose launch geometry does not "
        "flow from the tuning-cache lookup helper (paddle_tpu.tune."
        "kernel_config) — hardcoded block/grid choices freeze one "
        "device's tradeoffs into every device's launches; route the "
        "geometry through kernel_config so the autotuner's winners "
        "apply at trace time")),
    "wallclock-in-timing-path": (WARNING, "ast", (
        "a direct time.time() call in an inference/profiler-tier file — "
        "the wall clock is NTP-adjustable and non-monotonic, so durations "
        "computed from it can jump or go negative under clock slew; "
        "timing paths use time.perf_counter()/perf_counter_ns() (the "
        "clock every Tracer span and ServingStats reservoir is stamped "
        "with), or time.monotonic() for coarse uptime")),
    "collective-outside-shard-map": (ERROR, "ast", (
        "a lax collective (psum/all_gather/ppermute/...) inside an "
        "inference-tier compiled def that is never routed through "
        "shard_map — the mesh axis name is unbound outside shard_map, so "
        "the program either fails to trace or silently runs unsharded on "
        "one chip; wrap the step with shard_map before jitting")),
    "unbounded-observability-buffer": (WARNING, "ast", (
        "a list .append accumulation inside an observability-tier class "
        "(Stats/Tracer/Recorder/Window/Spool/...) with no visible bound "
        "— no capacity/maxlen/limit attribute, no deque(maxlen=), no "
        "pop-style eviction anywhere in the class — always-on telemetry "
        "that grows per request or per step leaks without bound on a "
        "long-running server; cap the buffer and count what it sheds "
        "(the Tracer-ring discipline)")),
    "host-sync-in-dispatch-path": (WARNING, "ast", (
        "int()/float()/np.asarray()/.item() applied to a step-program "
        "output inside an inference-tier dispatch/prestage path — the "
        "async pipeline's whole win is that dispatch launches WITHOUT "
        "materializing device results (JAX async dispatch); a host sync "
        "here re-serializes host packing with device compute, silently "
        "reverting the engine to its synchronous behavior; move the "
        "materialization to the completion seam")),
    "per-token-host-sync-in-decode-window": (WARNING, "ast", (
        "a host materialization (np.asarray()/np.array()/.item()/"
        "device_get()) reachable from a loop body handed to lax.scan/"
        "lax.while_loop in an inference-tier file — the decode-window "
        "contract is one host round trip per LAUNCH of K steps, with "
        "the drain reading committed tokens after the loop returns; a "
        "materialization inside the body's call graph forces one sync "
        "per iteration, quietly turning the K-step on-device window "
        "back into per-token round trips")),
    "host-copy-in-step-path": (WARNING, "ast", (
        "a KV-page transfer (np.asarray()/np.array()/jax.device_put()/"
        "device_get() on a page-pool-like operand) inside an "
        "inference-tier step hot phase (dispatch/prestage/complete) — "
        "the hierarchical-KV contract is that spill and restore copies "
        "cross the host/device boundary only in the step-boundary tier "
        "drain; a PCIe-sized page copy on the dispatch critical path "
        "stalls the async pipeline for milliseconds per page")),
    "nondeterministic-sim": (WARNING, "ast", (
        "a wall-clock read (time.time/perf_counter/monotonic), "
        "datetime.now/utcnow/today, or a global unseeded RNG call "
        "(random.random/randrange/... on the MODULE, not a seeded "
        "random.Random instance) inside a sim/ directory — the fleet "
        "simulator's hard invariant is virtual time and seeded "
        "randomness only: the same seed and workload must produce "
        "byte-identical records, and any real-clock or ambient-RNG "
        "dependence silently ties results to host speed or interpreter "
        "state; thread a random.Random(seed) through, and advance time "
        "via the event loop")),
    # race front end (race_rules.py): thread-role + lock-discipline
    "unguarded-shared-state": (ERROR, "race", (
        "an attribute written under a lock in one thread role is "
        "read/written lock-free in another — the class established a "
        "guard discipline for the attr and this access breaks it; take "
        "the lock, or annotate the method `# guarded-by: <attr>` when "
        "the caller provably holds it (validated at runtime under "
        "PT_ANALYSIS=strict by analysis.lock_check)")),
    "non-atomic-shared-rmw": (WARNING, "race", (
        "`self.x += 1`-style read-modify-write, lock-free, on an "
        "attribute multiple thread roles touch — the statement is a "
        "load, an op and a store; two racing roles lose an update")),
    "callback-under-lock": (WARNING, "race", (
        "a user callback (deliver/on_*/callback/hook) invoked while a "
        "lock is held — the callback can block or re-enter the class "
        "(classic deadlock seed); deliver outside the lock or suppress "
        "with the invariant that makes the hold load-bearing")),
    "blocking-call-in-event-loop": (WARNING, "race", (
        "a blocking call (bare .join(), queue .get(), time.sleep, "
        "lock .acquire(), engine .step()) reachable from asyncio-role "
        "code — it stalls the whole event loop (every connection), not "
        "one request; use the async equivalent or run_in_executor")),
}


def rule_severity(rule: str) -> str:
    return RULES[rule][0]


@dataclass(frozen=True)
class Location:
    file: str                 # repo-relative path or program name
    line: int = 0             # 1-based; 0 = whole file/program
    func: str = ""            # enclosing function / program / equation

    def __str__(self):
        s = f"{self.file}:{self.line}" if self.line else self.file
        return f"{s} ({self.func})" if self.func else s


@dataclass
class Finding:
    rule: str
    severity: str
    location: Location
    message: str
    trail: tuple = field(default_factory=tuple)   # ((file, line, func), ...)

    @property
    def fingerprint(self) -> str:
        # line-free so baselines survive unrelated edits above the finding
        key = "|".join((self.rule, self.location.file, self.location.func,
                        self.message))
        return hashlib.sha1(key.encode()).hexdigest()[:16]

    def to_dict(self) -> dict:
        return {
            "rule": self.rule,
            "severity": self.severity,
            "file": self.location.file,
            "line": self.location.line,
            "func": self.location.func,
            "message": self.message,
            "trail": [list(t) for t in self.trail],
            "fingerprint": self.fingerprint,
        }


# ---------------------------------------------------------------------------
# baseline file
# ---------------------------------------------------------------------------

def load_baseline(path) -> set:
    """Accepted-finding fingerprints, or an empty set when no file."""
    if not path or not os.path.exists(path):
        return set()
    with open(path) as f:
        data = json.load(f)
    return {e["fingerprint"] for e in data.get("accepted", [])}

def save_baseline(path, findings, reason: str = "accepted") -> None:
    entries = [{
        "fingerprint": f.fingerprint,
        "rule": f.rule,
        "location": str(f.location),
        "message": f.message,
        "reason": reason,
    } for f in findings]
    entries.sort(key=lambda e: (e["location"], e["rule"]))
    with open(path, "w") as fp:
        json.dump({"version": 1, "accepted": entries}, fp, indent=2)
        fp.write("\n")


def filter_baseline(findings, baseline: set):
    return [f for f in findings if f.fingerprint not in baseline]


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

def _sort_key(f: Finding):
    return (SEVERITIES.index(f.severity), f.location.file, f.location.line,
            f.rule)


def findings_to_json(findings, **extra) -> str:
    counts = {s: sum(1 for f in findings if f.severity == s)
              for s in SEVERITIES}
    doc = {"counts": counts,
           "findings": [f.to_dict() for f in sorted(findings, key=_sort_key)]}
    doc.update(extra)
    return json.dumps(doc, indent=2)


def format_text(findings) -> str:
    lines = []
    for f in sorted(findings, key=_sort_key):
        lines.append(f"{f.severity:7s} {f.rule:20s} {f.location}  "
                     f"{f.message}")
        for file, line, func in f.trail:
            lines.append(f"        via {file}:{line} in {func}")
    counts = {s: sum(1 for f in findings if f.severity == s)
              for s in SEVERITIES}
    lines.append(f"graft-lint: {counts[ERROR]} error(s), "
                 f"{counts[WARNING]} warning(s), {counts[INFO]} info")
    return "\n".join(lines)
