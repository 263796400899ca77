"""jaxpr front end: abstract-trace a compiled program and analyze it.

The AST pass reads source; this pass reads what XLA will actually be
handed.  A ``ProgramSpec`` names one program the repo compiles (the
serving prefill/chunked/decode steps, the captured train step), carries
the UNjitted callable plus example arguments (abstracted to
ShapeDtypeStructs — nothing executes, nothing allocates) and the
donation the wrapper declares.  ``analyze_program`` traces it once with
``jax.make_jaxpr`` and runs four passes over the equations:

  donation   — large inputs (>= ``large_bytes``) whose shape+dtype
               matches an output but which are not donated: the KV-pool
               /params copy-per-call hazard the serving engine exists
               to avoid.  Matching is multiset (an output "slot" is
               consumed by the donated input it aliases first).
  transfer   — callback primitives (pure/io/debug callback) anywhere in
               the program, including inside scan/cond/while bodies: a
               host round-trip per execution.
  dtype      — for programs declared bf16/f16: every
               convert_element_type that widens the declared compute
               dtype to f32/f64, reported PER EQUATION with the user
               source trail (the model line that wrote the upcast, not
               the lowering internals).
  dead       — equations whose outputs never reach a program output,
               inputs nothing reads (wasted transfer + recompile key),
               and pass-through outputs.

Everything reports through the shared ``Finding`` model, so jaxpr
findings baseline/suppress/format exactly like AST ones.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.extend.core import Literal

from .findings import (ERROR, INFO, WARNING, Finding, Location,
                       rule_severity)

__all__ = ["ProgramSpec", "analyze_program", "analyze_programs"]

_LOW_PRECISION = ("bfloat16", "float16")
_WIDE = ("float32", "float64")


@dataclass
class ProgramSpec:
    """One compiled program to analyze: fn is the UNjitted callable."""
    name: str
    fn: object
    args: tuple
    donate_argnums: tuple = ()
    declared_dtype: object = None     # bf16/f16 => dtype pass is armed
    large_bytes: int = 1 << 20        # donation/dead-input "large" floor
    kwargs: dict = field(default_factory=dict)


def _abstract(tree):
    """Map every leaf to a ShapeDtypeStruct so tracing never allocates."""
    def one(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(np.shape(x), x.dtype)
        return x                       # python scalar: traces as weak type
    return jax.tree_util.tree_map(one, tree)


def _nbytes(aval) -> int:
    return int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize


def _trail(eqn, limit: int = 3) -> tuple:
    """User-source frames for an equation, innermost first."""
    from jax._src import source_info_util
    frames = source_info_util.user_frames(eqn.source_info.traceback)
    return tuple((f.file_name, f.start_line, f.function_name)
                 for f in itertools.islice(frames, limit))


def _eqn_loc(name, eqn) -> Location:
    trail = _trail(eqn, limit=1)
    if trail:
        file, line, func = trail[0]
        return Location(file, line, f"{name}:{eqn.primitive.name}")
    return Location(name, 0, eqn.primitive.name)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            j = getattr(item, "jaxpr", None)     # ClosedJaxpr
            if j is not None:
                yield j
            elif hasattr(item, "eqns"):          # raw Jaxpr
                yield item


def _walk_eqns(jaxpr):
    """Every equation, recursing into scan/while/cond/pjit bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


def _arg_leaves(spec):
    """(argnum, path, leaf) per flattened leaf, in make_jaxpr invar order."""
    out = []
    for i, arg in enumerate(spec.args):
        leaves, _ = jax.tree_util.tree_flatten_with_path(arg)
        for path, leaf in leaves:
            out.append((i, jax.tree_util.keystr(path), leaf))
    return out


# ---------------------------------------------------------------------------
# passes
# ---------------------------------------------------------------------------

def _donation_pass(spec, jaxpr, invar_info, findings):
    closed = jaxpr
    jx = closed.jaxpr
    donated = set(spec.donate_argnums)
    # multiset of output avals available for aliasing, minus pass-throughs
    out_slots = {}
    invar_set = set(map(id, jx.invars))
    for v in jx.outvars:
        if isinstance(v, Literal) or id(v) in invar_set:
            continue
        key = (tuple(v.aval.shape), str(v.aval.dtype))
        out_slots[key] = out_slots.get(key, 0) + 1
    # donated inputs consume matching slots first
    for v, (argnum, path, _) in zip(jx.invars, invar_info):
        key = (tuple(v.aval.shape), str(v.aval.dtype))
        if argnum in donated and out_slots.get(key, 0) > 0:
            out_slots[key] -= 1
    for v, (argnum, path, _) in zip(jx.invars, invar_info):
        if argnum in donated:
            continue
        if _nbytes(v.aval) < spec.large_bytes:
            continue
        key = (tuple(v.aval.shape), str(v.aval.dtype))
        if out_slots.get(key, 0) > 0:
            out_slots[key] -= 1
            findings.append(Finding(
                "undonated-buffer", rule_severity("undonated-buffer"),
                Location(spec.name, 0, f"arg{argnum}{path}"),
                f"input arg{argnum}{path} "
                f"({key[1]}{list(key[0])}, {_nbytes(v.aval):,} bytes) "
                f"matches an output but is not donated — every call "
                f"copies it; add it to donate_argnums"))


def _transfer_pass(spec, jaxpr, findings):
    for eqn in _walk_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        if "callback" in name or name in ("infeed", "outfeed"):
            findings.append(Finding(
                "host-callback", rule_severity("host-callback"),
                _eqn_loc(spec.name, eqn),
                f"`{name}` primitive inside compiled program "
                f"{spec.name!r} — a device->host round-trip on every "
                f"execution", trail=_trail(eqn)))


def _dtype_pass(spec, jaxpr, findings):
    declared = np.dtype(spec.declared_dtype).name \
        if spec.declared_dtype is not None else None
    if declared not in _LOW_PRECISION:
        return
    for eqn in _walk_eqns(jaxpr.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        new = np.dtype(eqn.params.get("new_dtype")).name
        src = eqn.invars[0].aval
        if new in _WIDE and np.dtype(src.dtype).name == declared:
            findings.append(Finding(
                "dtype-promotion", rule_severity("dtype-promotion"),
                _eqn_loc(spec.name, eqn),
                f"{declared}{list(src.shape)} upcast to {new} inside "
                f"declared-{declared} program {spec.name!r} "
                f"({_nbytes(src):,} -> "
                f"{_nbytes(src) * np.dtype(new).itemsize // src.dtype.itemsize:,}"
                f" bytes)", trail=_trail(eqn)))


def _sweep_dead(eqns, live):
    """Backward liveness over one equation list; returns (dead eqns in
    program order, live variable ids grown to cover every read)."""
    dead = []
    for eqn in reversed(eqns):
        if {id(v) for v in eqn.outvars} & live:
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    live.add(id(v))
        else:
            dead.append(eqn)
    dead.reverse()
    return dead, live


def _dead_pass(spec, jaxpr, invar_info, findings):
    jx = jaxpr.jaxpr

    def sweep(body, live, where):
        dead, live = _sweep_dead(body.eqns, live)
        for eqn in dead:
            findings.append(Finding(
                "dead-code", rule_severity("dead-code"),
                _eqn_loc(spec.name, eqn),
                f"`{eqn.primitive.name}` result never reaches an output "
                f"of {where} (dead computation)",
                trail=_trail(eqn)))
        # recurse into the bodies of LIVE structured equations: an
        # equation dead inside a scan/while/cond body wastes FLOPs every
        # ITERATION even though the loop itself is live.  All sub-jaxpr
        # outvars count as live (which outputs the outer primitive
        # consumes is primitive-specific; conservative beats wrong), and
        # dead equations' bodies are skipped — the outer report covers
        # them.
        dead_ids = {id(e) for e in dead}
        for eqn in body.eqns:
            if id(eqn) in dead_ids:
                continue
            for sub in _sub_jaxprs(eqn):
                sub_live = {id(v) for v in sub.outvars
                            if not isinstance(v, Literal)}
                sweep(sub, sub_live,
                      f"the `{eqn.primitive.name}` body in {spec.name!r}")
        return live

    live = sweep(jx, {id(v) for v in jx.outvars
                      if not isinstance(v, Literal)}, repr(spec.name))
    outvar_ids = {id(v) for v in jx.outvars}
    for v, (argnum, path, _) in zip(jx.invars, invar_info):
        if id(v) not in live and id(v) not in outvar_ids:
            sev = ERROR if _nbytes(v.aval) >= spec.large_bytes \
                else rule_severity("dead-input")
            findings.append(Finding(
                "dead-input", sev,
                Location(spec.name, 0, f"arg{argnum}{path}"),
                f"input arg{argnum}{path} ({v.aval.dtype}"
                f"{list(v.aval.shape)}) is never read by {spec.name!r} — "
                f"wasted transfer and recompile key"))
        elif id(v) in outvar_ids:
            findings.append(Finding(
                "passthrough-output", INFO,
                Location(spec.name, 0, f"arg{argnum}{path}"),
                f"input arg{argnum}{path} is returned untouched by "
                f"{spec.name!r}"))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def analyze_program(spec: ProgramSpec) -> list:
    """Trace ``spec`` abstractly and run the pass pipeline over it."""
    args = _abstract(spec.args)
    kwargs = _abstract(spec.kwargs)
    jaxpr = jax.make_jaxpr(spec.fn)(*args, **kwargs)
    invar_info = _arg_leaves(spec)
    if len(invar_info) != len(jaxpr.jaxpr.invars):
        # kwargs (or non-array leaves) shifted the flat order: fall back
        # to positionless labels rather than mislabeling argnums
        invar_info = [(-1, f"[flat{i}]", None)
                      for i in range(len(jaxpr.jaxpr.invars))]
    findings = []
    _donation_pass(spec, jaxpr, invar_info, findings)
    _transfer_pass(spec, jaxpr, findings)
    _dtype_pass(spec, jaxpr, findings)
    _dead_pass(spec, jaxpr, invar_info, findings)
    return findings


def analyze_programs(specs) -> dict:
    """Findings per spec name: {name: [Finding, ...]}."""
    return {spec.name: analyze_program(spec) for spec in specs}
