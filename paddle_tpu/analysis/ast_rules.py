"""Python-AST front end: tracer-misuse lint over framework source.

The jaxpr analyzer sees programs the repo actually compiles; this pass
sees the SOURCE, so it catches hazards that never survive to a jaxpr —
code that would fail on a tracer at runtime (numpy calls, ``float()`` on
a traced argument, ``if`` on a tracer) or that silently recompiles
(``jax.jit`` rebuilt per call).  Pure stdlib: importable without jax, so
the pytest plugin and import-time enforce stay cheap.

What counts as a COMPILED body is resolved per file, conservatively, by
fixpoint:

  roots:  ``@jax.jit`` / ``@partial(jax.jit, ...)`` decorated defs; any
          FunctionDef whose name is passed to ``jax.jit`` or to a traced
          transform (``lax.scan``/``cond``/``while_loop``/``fori_loop``,
          ``vmap``/``pmap``/``grad``/``value_and_grad``/``checkpoint``/
          ``remat``/``custom_vjp``...)
  close:  defs nested inside a compiled def, and defs CALLED by name
          from a compiled body (tracing executes them), join the set.

Rule scope is deliberately two-tier.  Rules about OPERATIONS that never
belong in a trace (numpy calls, ``.item()``/``.tolist()``/``.numpy()``)
apply to the whole fixpoint set.  Rules about ARGUMENTS being tracers
(``if`` on a param, ``float(param)``) apply only to the ROOTS — a root's
parameters are definitely traced (minus ``static_argnums``), while a
closure-called helper's parameters are routinely static Python config
(``causal`` flags, padded sizes), and flagging those would drown the
signal.  ``is None`` / ``isinstance`` / ``hasattr`` / ``len`` tests are
structure checks, legal on tracers, and never count as branching.

Suppression: ``# graftlint: disable=rule[,rule]`` on the finding's line
or on its enclosing ``def`` line; ``# graftlint: skip-file`` near the
top of a file (fixture trees use this to stay out of the repo lint).
"""
from __future__ import annotations

import ast
import os
import re

from .findings import ERROR, WARNING, Finding, Location, rule_severity

__all__ = ["lint_file", "lint_source", "lint_paths", "collect_py_files"]

_JIT_NAMES = {("jax", "jit"), ("jit",)}
_TRANSFORM_NAMES = {
    ("jax", "vmap"), ("vmap",), ("jax", "pmap"), ("pmap",),
    ("jax", "grad"), ("grad",), ("jax", "value_and_grad"),
    ("value_and_grad",), ("jax", "checkpoint"), ("jax", "remat"),
    ("jax", "custom_vjp"), ("jax", "custom_jvp"),
    ("jax", "lax", "scan"), ("lax", "scan"), ("jax", "lax", "map"),
    ("lax", "map"), ("jax", "lax", "cond"), ("lax", "cond"),
    ("jax", "lax", "switch"), ("lax", "switch"),
    ("jax", "lax", "while_loop"), ("lax", "while_loop"),
    ("jax", "lax", "fori_loop"), ("lax", "fori_loop"),
}
_HOST_SYNC_ATTRS = {"item", "tolist", "numpy", "block_until_ready"}
_COERCIONS = {"float", "int", "bool"}

_ATTEN_RE = re.compile(r"atten", re.IGNORECASE)


def _declared_attention_kinds(tree) -> int:
    """How many attention kinds a module declares for its layers: the
    length of a module-level ``ATTENTION_KINDS = ("...", ...)`` literal
    of distinct names; 1 where there is none."""
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "ATTENTION_KINDS"
                for t in node.targets) \
                and isinstance(node.value, (ast.Tuple, ast.List)):
            names = {e.value for e in node.value.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, str)}
            return max(1, len(names))
    return 1

# real-clock reads and global-RNG calls the simulator tier must not
# make (nondeterministic-sim); seeded random.Random instances are fine
_WALL_CLOCK_FNS = {"time", "time_ns", "perf_counter", "perf_counter_ns",
                   "monotonic", "monotonic_ns"}
_GLOBAL_RNG_FNS = {"random", "randrange", "randint", "uniform", "choice",
                   "choices", "shuffle", "sample", "gauss",
                   "normalvariate", "lognormvariate", "expovariate",
                   "paretovariate", "betavariate", "gammavariate",
                   "triangular", "vonmisesvariate", "weibullvariate",
                   "getrandbits", "randbytes"}

# mesh collectives whose axis name binds only under shard_map
_COLLECTIVES = {"psum", "all_gather", "psum_scatter", "ppermute",
                "all_to_all", "pmean", "pmax", "pmin"}

# KV-PAGE pool names (kc/vc/k_cache/kv_cache/page_pool...); scale pools
# (_ks/_vs/scales) deliberately don't match — f32 scales are the contract
_KV_PAGE_RE = re.compile(
    r"(^|_)(kc|vc)$|(k|key|v|value)_?cache|kv_?(cache|pages?|pool)"
    r"|page_?pool", re.IGNORECASE)
_ALLOC_FNS = {"zeros", "ones", "empty", "full",
              "zeros_like", "ones_like", "empty_like", "full_like"}


def _mentions_float32(call) -> bool:
    for n in ast.walk(call):
        if isinstance(n, ast.Attribute) and n.attr == "float32":
            return True
        if isinstance(n, ast.Name) and n.id == "float32":
            return True
        if isinstance(n, ast.Constant) and n.value == "float32":
            return True
    return False


def _kv_dtype_test(test) -> bool:
    """An `if` test comparing a kv_dtype-ish name against "int8"."""
    has_kv = any(
        (isinstance(n, ast.Name) and "kv_dtype" in n.id)
        or (isinstance(n, ast.Attribute) and "kv_dtype" in n.attr)
        for n in ast.walk(test))
    has_i8 = any(isinstance(n, ast.Constant) and n.value == "int8"
                 for n in ast.walk(test))
    return has_kv and has_i8

def _weight_dtype_test(test) -> bool:
    """An `if` test comparing a weight_dtype-ish name to "float32"."""
    has_w = any(
        (isinstance(n, ast.Name) and "weight_dtype" in n.id)
        or (isinstance(n, ast.Attribute) and "weight_dtype" in n.attr)
        for n in ast.walk(test))
    has_f32 = any(isinstance(n, ast.Constant) and n.value == "float32"
                  for n in ast.walk(test))
    return has_w and has_f32


# WEIGHT-POOL entry names (the llama decode_params vocabulary); the
# quantized pools (name_q) and their scales (name_s) deliberately don't
# match — contracting against those is exactly what the helper does
_WEIGHT_NAMES = {"wq", "wk", "wv", "wo", "gate", "up", "down",
                 "embed", "head", "lm_head"}
_WEIGHT_RE = re.compile(
    r"(^|_)(wq|wk|wv|wo|gate|up|down|embed|head|weights?)$",
    re.IGNORECASE)
_MATMUL_FNS = {"matmul", "dot", "einsum", "dot_general"}


def _weight_operand(node) -> str | None:
    """'wq' for p["wq"] / params.wq / a bare weight-like Name; None for
    anything else (including name_q/name_s quantized-pool entries)."""
    if isinstance(node, ast.Subscript):
        sl = node.slice
        if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
            name = sl.value
            if name.endswith(("_q", "_s")):
                return None
            if name in _WEIGHT_NAMES or _WEIGHT_RE.search(name):
                return name
        return None
    name = None
    if isinstance(node, ast.Attribute):
        name = node.attr
    elif isinstance(node, ast.Name):
        name = node.id
    if name and not name.endswith(("_q", "_s")) \
            and (name in _WEIGHT_NAMES or _WEIGHT_RE.search(name)):
        return name
    return None


def _weight_matmul(node) -> str | None:
    """The weight name when `node` is a dense contraction against a
    weight-pool entry: `x @ p["wq"]`, jnp.matmul/dot/einsum(...), or
    lax.dot_general(...)."""
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.MatMult):
        for side in (node.left, node.right):
            w = _weight_operand(side)
            if w:
                return w
        # `h @ p["wq"].astype(...)` — unwrap one call layer per side
        for side in (node.left, node.right):
            if isinstance(side, ast.Call) and side.args:
                w = _weight_operand(side.args[0])
                if w:
                    return w
            if isinstance(side, ast.Call) \
                    and isinstance(side.func, ast.Attribute):
                w = _weight_operand(side.func.value)
                if w:
                    return w
        return None
    if isinstance(node, ast.Call):
        dd = _dotted(node.func) or ()
        if dd and dd[-1] in _MATMUL_FNS:
            for arg in node.args:
                w = _weight_operand(arg)
                if w:
                    return w
    return None


_DISABLE_RE = re.compile(r"#\s*graftlint:\s*disable=([\w\-, ]+)")
_DISABLE_NEXT_RE = re.compile(r"#\s*graftlint:\s*disable-next=([\w\-, ]+)")
_SKIP_RE = re.compile(r"#\s*graftlint:\s*skip-file")


def _dotted(node):
    """('jax','lax','scan') for jax.lax.scan; None for anything fancier."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


class _FileCtx:
    def __init__(self, path, text):
        self.path = path
        self.tree = ast.parse(text)
        self.lines = text.splitlines()
        self.parents = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parents[id(child)] = node
        # numpy import aliases in this file ("np", "numpy", ...); jnp is jax
        self.np_aliases = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "numpy":
                        self.np_aliases.add(a.asname or "numpy")
        self.disabled = {}            # line -> set of rule names
        for i, line in enumerate(self.lines, 1):
            m = _DISABLE_NEXT_RE.search(line)
            if m:
                rules = {r.strip() for r in m.group(1).split(",")}
                self.disabled.setdefault(i + 1, set()).update(rules)
                continue
            m = _DISABLE_RE.search(line)
            if m:
                rules = {r.strip() for r in m.group(1).split(",")}
                self.disabled.setdefault(i, set()).update(rules)
        self.defs = [n for n in ast.walk(self.tree)
                     if isinstance(n, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))]
        self.by_name = {}
        for d in self.defs:
            self.by_name.setdefault(d.name, []).append(d)

    def ancestors(self, node):
        n = self.parents.get(id(node))
        while n is not None:
            yield n
            n = self.parents.get(id(n))

    def qualname(self, node) -> str:
        parts = [node.name] if hasattr(node, "name") else []
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(anc.name)
        return ".".join(reversed(parts))

    def suppressed(self, rule, node) -> bool:
        lines = {getattr(node, "lineno", 0)}
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                lines.add(anc.lineno)
                break
        for ln in lines:
            rules = self.disabled.get(ln)
            if rules and (rule in rules or "all" in rules):
                return True
        return False


def _is_jit_ref(node) -> bool:
    d = _dotted(node)
    return d in _JIT_NAMES if d else False


def _static_params(call, fn) -> set:
    """Param names a jit call pins static (static_argnums/static_argnames
    with literal values); best-effort — non-literal specs pin nothing."""
    names = []
    a = fn.args
    ordered = [p.arg for p in a.posonlyargs + a.args]
    for kw in call.keywords:
        try:
            val = ast.literal_eval(kw.value)
        except (ValueError, SyntaxError):
            continue
        items = val if isinstance(val, (tuple, list)) else [val]
        if kw.arg == "static_argnums":
            names.extend(ordered[i] for i in items if isinstance(i, int)
                         and i < len(ordered))
        elif kw.arg == "static_argnames":
            names.extend(str(i) for i in items)
    return set(names)


def _compiled_defs(ctx: _FileCtx):
    """(fixpoint set of compiled FunctionDefs, {root def: static params}).

    Roots are defs handed directly to jit/a transform — their params are
    certainly traced.  The fixpoint closure adds nested defs and defs
    called by name from compiled bodies (tracing executes them), whose
    params may well be static — tracer-ARGUMENT rules skip those.
    """
    compiled = set()
    roots = {}

    def seed_name(name, statics=frozenset()):
        for d in ctx.by_name.get(name, ()):
            compiled.add(d)
            roots.setdefault(d, set()).update(statics)

    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jit_ref(dec):
                    compiled.add(node)
                    roots.setdefault(node, set())
                elif isinstance(dec, ast.Call):
                    if _is_jit_ref(dec.func):
                        compiled.add(node)
                        roots.setdefault(node, set()).update(
                            _static_params(dec, node))
                    elif (_dotted(dec.func) or ())[-1:] == ("partial",) \
                            and dec.args and _is_jit_ref(dec.args[0]):
                        compiled.add(node)
                        roots.setdefault(node, set()).update(
                            _static_params(dec, node))
        elif isinstance(node, ast.Call):
            d = _dotted(node.func)
            if d in _JIT_NAMES:
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        for fd in ctx.by_name.get(arg.id, ()):
                            seed_name(arg.id, _static_params(node, fd))
            elif d in _TRANSFORM_NAMES:
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        seed_name(arg.id)

    changed = True
    while changed:
        changed = False
        for d in list(compiled):
            for node in ast.walk(d):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and node is not d and node not in compiled:
                    compiled.add(node)
                    changed = True
                elif isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Name):
                    for callee in ctx.by_name.get(node.func.id, ()):
                        if callee not in compiled:
                            compiled.add(callee)
                            changed = True
    return compiled, roots


def _params_of(fn) -> set:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    for extra in (a.vararg, a.kwarg):
        if extra is not None:
            names.append(extra.arg)
    return {n for n in names if n != "self"}


def _mutable_default(node) -> bool:
    if isinstance(node, (ast.List, ast.Dict, ast.Set)):
        return True
    return (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id in ("list", "dict", "set"))


def _walk_own(fn):
    """Walk fn's subtree, stopping at nested def boundaries (nested defs
    are linted on their own visit)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            stack.extend(ast.iter_child_nodes(node))


_STRUCTURE_FNS = {"isinstance", "hasattr", "len", "getattr", "callable",
                  "type"}


def _dynamic_names(test) -> set:
    """Names in a test expression that would concretize a tracer —
    skipping structure checks (`x is None`, isinstance/hasattr/len) that
    are legal on tracers."""
    names = set()

    def walk(n):
        if isinstance(n, ast.Compare) \
                and all(isinstance(op, (ast.Is, ast.IsNot)) for op in n.ops):
            return
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                and n.func.id in _STRUCTURE_FNS:
            return
        if isinstance(n, ast.Name):
            names.add(n.id)
        for c in ast.iter_child_nodes(n):
            walk(c)

    walk(test)
    return names


def lint_source(text: str, path: str = "<string>") -> list:
    if _SKIP_RE.search("\n".join(text.splitlines()[:5])):
        return []
    ctx = _FileCtx(path, text)
    compiled, roots = _compiled_defs(ctx)
    findings = []

    def emit(rule, node, message, severity=None):
        if ctx.suppressed(rule, node):
            return
        fn = ""
        for anc in [node] + list(ctx.ancestors(node)):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn = ctx.qualname(anc)
                break
        findings.append(Finding(
            rule, severity or rule_severity(rule),
            Location(path, getattr(node, "lineno", 0), fn), message))

    # ---- file-wide rules -------------------------------------------------
    for d in ctx.defs:
        in_jit = d in compiled
        for default in list(d.args.defaults) + \
                [k for k in d.args.kw_defaults if k is not None]:
            if _mutable_default(default):
                emit("mutable-default-arg", d,
                     f"def {d.name}(...) has a mutable default argument"
                     + (" inside a compiled path (hidden retrace key)"
                        if in_jit else ""),
                     severity=ERROR if in_jit else WARNING)
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call) and _is_jit_ref(node.func)):
            continue
        parent = ctx.parents.get(id(node))
        if isinstance(parent, ast.Call) and parent.func is node:
            emit("unkeyed-jit", node,
                 "jax.jit(...) built and invoked in one expression — "
                 "recompiles every call; hoist the jitted fn")
            continue
        for anc in ctx.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                break
            if isinstance(anc, (ast.For, ast.While)):
                emit("unkeyed-jit", node,
                     "jax.jit(...) constructed inside a loop — one cache "
                     "entry per iteration (recompile hazard)")
                break

    # ---- compiled-body rules ---------------------------------------------
    for d in compiled:
        # traced params: only certain for tracing ROOTS, minus statics
        traced = (_params_of(d) - roots[d]) if d in roots else set()
        for node in _walk_own(d):
            if isinstance(node, ast.Call):
                dd = _dotted(node.func)
                if dd and dd[0] in ctx.np_aliases:
                    emit("numpy-in-jit", node,
                         f"numpy call `{'.'.join(dd)}(...)` inside "
                         f"jit-compiled `{d.name}` — escapes the trace or "
                         "fails on tracers")
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in _HOST_SYNC_ATTRS:
                    emit("host-sync-in-jit", node,
                         f"`.{node.func.attr}()` inside jit-compiled "
                         f"`{d.name}` forces a device->host sync")
                elif isinstance(node.func, ast.Name) \
                        and node.func.id in _COERCIONS and node.args:
                    touched = _dynamic_names(node.args[0])
                    if touched & traced:
                        emit("host-sync-in-jit", node,
                             f"`{node.func.id}()` coerces traced argument "
                             f"{sorted(touched & traced)[0]!r} inside "
                             f"jit-compiled `{d.name}` (concretization)")
            elif isinstance(node, (ast.If, ast.While, ast.IfExp)):
                hit = sorted(_dynamic_names(node.test) & traced)
                if hit:
                    kind = ("while" if isinstance(node, ast.While) else "if")
                    emit("tracer-branch", node,
                         f"Python `{kind}` on traced argument {hit[0]!r} "
                         f"inside jit-compiled `{d.name}` — use "
                         "lax.cond/jnp.where")

    # ---- attention-program-budget (serving tier only) --------------------
    # What the budget protects is "no compile per request": the attention-
    # bearing compiled program KINDS of an engine are bounded by what its
    # models' layers are, not by what requests do.  A module of the
    # inference tier declares the attention kinds its layers may have
    # (``ATTENTION_KINDS = ("gqa", "mla")``, a literal tuple of names) and
    # may hold that many attention program kinds; without a declaration
    # the budget is ONE (the ragged step).  A jit root or pallas_call def
    # beyond it is a phase-special kernel sneaking back in.
    if "inference" in re.split(r"[\\/]", path):
        progs = set(roots)
        for d in ctx.defs:
            if any(isinstance(n, ast.Call)
                   and (_dotted(n.func) or ())[-1:] == ("pallas_call",)
                   for n in ast.walk(d)):
                progs.add(d)
        # count outermost program defs only: a nested def (scan body,
        # kernel closure) belongs to its enclosing program
        tops = [d for d in progs
                if not any(a in progs for a in ctx.ancestors(d))]

        def _mentions_attention(d):
            for n in ast.walk(d):
                if isinstance(n, ast.Attribute) and _ATTEN_RE.search(n.attr):
                    return True
                if isinstance(n, ast.Name) and _ATTEN_RE.search(n.id):
                    return True
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and _ATTEN_RE.search(n.name):
                    return True
            return False

        att = sorted((d for d in tops if _mentions_attention(d)),
                     key=lambda d: d.lineno)
        # kind identity is the def NAME, mirroring the runtime
        # compile_counts budget keyed by program kind: dtype variants of
        # the one ragged step (float32 vs quantized int8 pages) share a
        # name and an engine only ever compiles one of them, while a
        # phase-special kernel sneaking back in arrives under its own
        # name (decode_step, prefill_attn, ...)
        kinds = []
        for d in att:
            if all(d.name != k.name for k in kinds):
                kinds.append(d)
        budget = _declared_attention_kinds(ctx.tree)
        for d in kinds[budget:]:
            emit("attention-program-budget", d,
                 f"compiled def `{d.name}` is attention program kind "
                 f"{kinds.index(d) + 1} in the serving tier (first: "
                 f"`{kinds[0].name}`) — budget is {budget}: one per "
                 "attention kind the module's ATTENTION_KINDS declares (1 "
                 "without it); route rows through the ragged step instead")

        # ---- quantized-kv-float32-page (serving tier only) ---------------
        # In the branch an engine takes when configured kv_dtype="int8",
        # the page pools must be int8 (with f32 SCALE rows in a parallel
        # pool — scale names don't look like page names).  A float32
        # allocation bound to a KV-page-like name there silently forfeits
        # the whole HBM win the quantized format exists for.
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.If) and _kv_dtype_test(node.test)):
                continue
            quant = node.body
            if isinstance(node.test, ast.Compare) and node.test.ops \
                    and isinstance(node.test.ops[0], ast.NotEq):
                quant = node.orelse
            for stmt in quant:
                for n in ast.walk(stmt):
                    if not (isinstance(n, ast.Assign)
                            and isinstance(n.value, ast.Call)):
                        continue
                    dd = _dotted(n.value.func) or ()
                    if not dd or dd[-1] not in _ALLOC_FNS \
                            or not _mentions_float32(n.value):
                        continue
                    tname = next(
                        (t.attr if isinstance(t, ast.Attribute) else t.id
                         for t in n.targets
                         if isinstance(t, (ast.Attribute, ast.Name))),
                        None)
                    if tname and _KV_PAGE_RE.search(tname):
                        emit("quantized-kv-float32-page", n,
                             f"float32 KV-page allocation `{tname}` in "
                             "the quantized (kv_dtype == \"int8\") branch "
                             "— quantized engines store int8 pages with "
                             "f32 scale rows; a float32 page pool "
                             "silently forfeits the HBM win",
                             severity=WARNING)

        # ---- f32-weight-matmul-in-quantized-engine (serving tier only) ---
        # In the branch an engine takes when configured with a quantized
        # weight_dtype, every projection/MLP/head contraction must route
        # through the fused dequant-matmul helper over the int8/int4
        # pools (name_q + name_s scale rows).  A dense matmul against a
        # raw weight-pool entry there either KeyErrors on the quantized
        # pool or silently streams f32 weights — forfeiting the whole
        # 4x/8x weight-byte win the format exists for.
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.If)
                    and _weight_dtype_test(node.test)):
                continue
            quant = node.body
            if isinstance(node.test, ast.Compare) and node.test.ops \
                    and isinstance(node.test.ops[0], ast.Eq):
                quant = node.orelse
            for stmt in quant:
                for n in ast.walk(stmt):
                    w = _weight_matmul(n)
                    if w:
                        emit("f32-weight-matmul-in-quantized-engine", n,
                             f"dense matmul against weight `{w}` in the "
                             "quantized (weight_dtype != \"float32\") "
                             "branch — route the contraction through the "
                             "fused dequant-matmul helper over the "
                             f"`{w}_q`/`{w}_s` pools instead",
                             severity=WARNING)

        # ---- swallowed-exception (serving tier only) ---------------------
        # Fault-tolerance contract: failures in step/release/abort/recover
        # paths must SURFACE — the supervised watchdog classifies a crashed
        # step by catching its exception, and quarantine/page accounting
        # depend on release errors propagating.  A broad handler that only
        # passes (or logs and continues) converts a crash into a silent
        # hang or a leaked sequence.
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            fn = None
            for anc in ctx.ancestors(node):
                if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    fn = anc
                    break
            if fn is None or not _CRITICAL_RE.search(fn.name):
                continue
            if _broad_handler(node) and _swallowing_body(node):
                emit("swallowed-exception", node,
                     f"broad `except` in `{fn.name}` swallows the "
                     "exception (pass/log-and-continue) — step/release/"
                     "abort/recover paths must let failures surface for "
                     "the watchdog and quarantine logic")

        # ---- collective-outside-shard-map (serving tier only) -------------
        # TP contract: lax collectives bind their mesh axis name ("tp")
        # only under shard_map.  A collective in a compiled def never
        # routed through shard_map either fails to trace (unbound axis)
        # or runs unsharded on one chip.  Same name-based fixpoint as the
        # compiled set: ``shard_map(run, ...)`` marks every def named
        # ``run``, plus its nested defs and by-name callees.
        shardmapped = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) \
                    and (_dotted(node.func) or ())[-1:] == ("shard_map",):
                for arg in node.args[:1]:
                    if isinstance(arg, ast.Name):
                        shardmapped.update(ctx.by_name.get(arg.id, ()))
        changed = True
        while changed:
            changed = False
            for d in list(shardmapped):
                for node in ast.walk(d):
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)) \
                            and node not in shardmapped:
                        shardmapped.add(node)
                        changed = True
                    elif isinstance(node, ast.Call) \
                            and isinstance(node.func, ast.Name):
                        for callee in ctx.by_name.get(node.func.id, ()):
                            if callee not in shardmapped:
                                shardmapped.add(callee)
                                changed = True
        for d in compiled - shardmapped:
            for node in _walk_own(d):
                if not isinstance(node, ast.Call):
                    continue
                dd = _dotted(node.func)
                if dd and dd[-1] in _COLLECTIVES \
                        and ("lax" in dd or len(dd) == 1):
                    emit("collective-outside-shard-map", node,
                         f"`{'.'.join(dd)}` inside compiled `{d.name}`, "
                         "which is never handed to shard_map — the mesh "
                         "axis name is unbound here; wrap the step with "
                         "shard_map before jax.jit")

        # ---- host-sync-in-dispatch-path (serving tier only) ---------------
        # Async-pipeline contract: the dispatch section launches the step
        # program WITHOUT materializing its results — materialization
        # belongs to the completion seam.  Same name-based fixpoint as
        # the compiled set: defs named like dispatch/prestage, plus their
        # nested defs, by-name callees and self-method callees, form the
        # dispatch path; names assigned from a *launch*-ish call are the
        # step-program outputs.  int()/float()/np.asarray()/.item() on
        # one of those names inside the dispatch path forces the host
        # sync the pipeline exists to avoid.
        dispatch_set = {d for d in ctx.defs
                        if "dispatch" in d.name or "prestage" in d.name}
        changed = True
        while changed:
            changed = False
            for d in list(dispatch_set):
                for node in ast.walk(d):
                    callee = None
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)) \
                            and node not in dispatch_set:
                        dispatch_set.add(node)
                        changed = True
                        continue
                    if isinstance(node, ast.Call):
                        if isinstance(node.func, ast.Name):
                            callee = node.func.id
                        elif isinstance(node.func, ast.Attribute) \
                                and isinstance(node.func.value, ast.Name) \
                                and node.func.value.id == "self":
                            callee = node.func.attr
                    if callee is not None:
                        for cd in ctx.by_name.get(callee, ()):
                            if cd not in dispatch_set:
                                dispatch_set.add(cd)
                                changed = True
        # step-program output names: assigned from a call whose terminal
        # name mentions "launch", then propagated through plain ALIASES
        # only (x = sampled; x = sampled[0]) — a computed RHS (bucket
        # math, slicing arithmetic) launders the device handle into a
        # host value on its own and must not spread the taint
        def _alias_root(n):
            while isinstance(n, (ast.Subscript, ast.Attribute)):
                n = n.value
            return n.id if isinstance(n, ast.Name) else None

        outputs = set()
        changed = True
        while changed:
            changed = False
            for d in dispatch_set:
                for node in _walk_own(d):
                    if not isinstance(node, ast.Assign):
                        continue
                    tainted = False
                    if isinstance(node.value, ast.Call):
                        dd = _dotted(node.value.func) or ()
                        tainted = bool(dd) and "launch" in dd[-1]
                    if not tainted:
                        tainted = _alias_root(node.value) in outputs
                    if not tainted:
                        continue
                    for t in node.targets:
                        elts = t.elts if isinstance(t, ast.Tuple) else [t]
                        for e in elts:
                            if isinstance(e, ast.Name) \
                                    and e.id not in outputs:
                                outputs.add(e.id)
                                changed = True

        def _touches_output(expr) -> str | None:
            for n in ast.walk(expr):
                if isinstance(n, ast.Name) and n.id in outputs:
                    return n.id
            return None

        for d in dispatch_set:
            for node in _walk_own(d):
                if not isinstance(node, ast.Call):
                    continue
                hit = None
                how = None
                if isinstance(node.func, ast.Name) \
                        and node.func.id in _COERCIONS and node.args:
                    hit = _touches_output(node.args[0])
                    how = f"{node.func.id}()"
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "item":
                    hit = _touches_output(node.func.value)
                    how = ".item()"
                else:
                    dd = _dotted(node.func) or ()
                    if len(dd) >= 2 and dd[0] in ctx.np_aliases \
                            and dd[-1] in ("asarray", "array") and node.args:
                        hit = _touches_output(node.args[0])
                        how = f"{'.'.join(dd)}()"
                if hit is not None:
                    emit("host-sync-in-dispatch-path", node,
                         f"`{how}` on step-program output {hit!r} inside "
                         f"dispatch-path `{d.name}` — this blocks on the "
                         "in-flight device program and re-serializes host "
                         "packing with device compute; materialize in the "
                         "completion seam instead")

        # ---- per-token-host-sync-in-decode-window (serving tier only) -----
        # Decode-window contract: a body handed to lax.scan/lax.while_loop
        # runs entirely on device — attention, sampling epilogue, KV
        # append — and the host drains K committed tokens once per
        # LAUNCH, after the loop returns.  A host materialization
        # reachable from the body forces one sync per loop ITERATION,
        # quietly reverting the window to per-token round trips.  Seed:
        # defs passed by name (or as self-methods) to scan/while_loop;
        # closure adds nested defs plus by-name AND self-method callees
        # — the compiled fixpoint only follows by-name calls, so a
        # hazard buried in a self-method callee goes unseen by the
        # numpy-in-jit/host-sync-in-jit rules.  Name seeds resolve
        # SCOPE-LOCALLY (defs nested in the lax call's enclosing
        # function), the way Python resolves the closure actually
        # passed — a whole-file by_name lookup would collide the local
        # `step` body with an engine's `step` method and drag the whole
        # host dispatch graph into the loop set.
        def _enclosing_fn(node):
            return next((a for a in ctx.ancestors(node)
                         if isinstance(a, (ast.FunctionDef,
                                           ast.AsyncFunctionDef))), None)

        window_set = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            dd = _dotted(node.func) or ()
            if dd[-1:] not in (("scan",), ("while_loop",)) \
                    or not ("lax" in dd or len(dd) == 1):
                continue
            scope = _enclosing_fn(node)
            for arg in node.args:
                if isinstance(arg, ast.Name):
                    for fd in ctx.by_name.get(arg.id, ()):
                        if scope is None \
                                or any(a is scope
                                       for a in ctx.ancestors(fd)):
                            window_set.add(fd)
                elif isinstance(arg, ast.Attribute) \
                        and isinstance(arg.value, ast.Name) \
                        and arg.value.id == "self":
                    window_set.update(ctx.by_name.get(arg.attr, ()))
        changed = True
        while changed:
            changed = False
            for d in list(window_set):
                for node in ast.walk(d):
                    callee = None
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)) \
                            and node not in window_set:
                        window_set.add(node)
                        changed = True
                        continue
                    if isinstance(node, ast.Call):
                        if isinstance(node.func, ast.Name):
                            callee = node.func.id
                        elif isinstance(node.func, ast.Attribute) \
                                and isinstance(node.func.value, ast.Name) \
                                and node.func.value.id == "self":
                            callee = node.func.attr
                    if callee is not None:
                        for cd in ctx.by_name.get(callee, ()):
                            if cd not in window_set:
                                window_set.add(cd)
                                changed = True
        for d in window_set:
            for node in _walk_own(d):
                if not isinstance(node, ast.Call):
                    continue
                how = None
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "item":
                    how = ".item()"
                else:
                    dd = _dotted(node.func) or ()
                    if dd[-1:] == ("device_get",):
                        how = f"{'.'.join(dd)}()"
                    elif len(dd) >= 2 and dd[0] in ctx.np_aliases \
                            and dd[-1] in ("asarray", "array"):
                        how = f"{'.'.join(dd)}()"
                if how is not None:
                    emit("per-token-host-sync-in-decode-window", node,
                         f"`{how}` inside `{d.name}`, reachable from a "
                         "lax.scan/while_loop body — this materializes "
                         "on the host once per window iteration, turning "
                         "the K-step on-device decode window back into "
                         "per-token round trips; drain committed tokens "
                         "once per launch, after the loop returns")

        # ---- host-copy-in-step-path (serving tier only) --------------------
        # Hierarchical-KV contract: spill and restore transfers — a KV
        # page crossing the host/device boundary — happen at the STEP
        # BOUNDARY (the tier drain), never inside the step's hot phases.
        # dispatch/prestage/complete sit on the critical path of every
        # token; a PCIe-sized page copy there stalls the async pipeline
        # for milliseconds per page.  Seed: defs named like the hot
        # phases, minus anything drain-named (the drain IS the
        # sanctioned boundary); close over nested defs and by-name/
        # self-method callees, the dispatch-path fixpoint — drain-named
        # callees stay out so `self._drain_kv_tier()` never drags the
        # drain body into the hot set.  Flag: a transfer call
        # (np.asarray/np.array/jax.device_put/device_get) whose operand
        # reads like a KV page pool.
        hot_set = {d for d in ctx.defs
                   if ("dispatch" in d.name or "prestage" in d.name
                       or "complete" in d.name)
                   and "drain" not in d.name}
        changed = True
        while changed:
            changed = False
            for d in list(hot_set):
                for node in ast.walk(d):
                    callee = None
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)) \
                            and node not in hot_set:
                        if "drain" not in node.name:
                            hot_set.add(node)
                            changed = True
                        continue
                    if isinstance(node, ast.Call):
                        if isinstance(node.func, ast.Name):
                            callee = node.func.id
                        elif isinstance(node.func, ast.Attribute) \
                                and isinstance(node.func.value, ast.Name) \
                                and node.func.value.id == "self":
                            callee = node.func.attr
                    if callee is not None and "drain" not in callee:
                        for cd in ctx.by_name.get(callee, ()):
                            if cd not in hot_set:
                                hot_set.add(cd)
                                changed = True

        def _kv_page_operand(expr) -> str | None:
            for n in ast.walk(expr):
                name = n.id if isinstance(n, ast.Name) else (
                    n.attr if isinstance(n, ast.Attribute) else None)
                if name and _KV_PAGE_RE.search(name):
                    return name
            return None

        for d in hot_set:
            for node in _walk_own(d):
                if not isinstance(node, ast.Call) or not node.args:
                    continue
                dd = _dotted(node.func) or ()
                if not dd:
                    continue
                np_copy = len(dd) >= 2 and dd[0] in ctx.np_aliases \
                    and dd[-1] in ("asarray", "array")
                transfer = dd[-1] in ("device_put", "device_get")
                if not (np_copy or transfer):
                    continue
                hit = _kv_page_operand(node.args[0])
                if hit is not None:
                    emit("host-copy-in-step-path", node,
                         f"`{'.'.join(dd)}()` moves KV page operand "
                         f"{hit!r} across the host/device boundary "
                         f"inside step hot phase `{d.name}` — spill and "
                         "restore transfers belong in the step-boundary "
                         "tier drain, where they overlap with host "
                         "scheduling instead of stalling dispatch")

    # ---- untuned-pallas-launch (ops/pallas only) -------------------------
    # Autotuner contract: every Pallas launch's geometry (block sizes,
    # grid blocking, page-walk width) flows from the tuning-cache lookup
    # helper `paddle_tpu.tune.kernel_config`, so per-device winners apply
    # at trace time.  Same name-based fixpoint as the compiled set: a def
    # that references kernel_config is tuned, and so is any def calling a
    # tuned def (the lookup usually lives in a small `_fa_blocks`-style
    # helper the launcher calls).
    if "pallas" in re.split(r"[\\/]", path):
        tuned = set()
        for d in ctx.defs:
            for n in ast.walk(d):
                name = n.id if isinstance(n, ast.Name) else (
                    n.attr if isinstance(n, ast.Attribute) else None)
                if name in ("kernel_config", "kernel_config_with_meta"):
                    tuned.add(d)
                    break
        changed = True
        while changed:
            changed = False
            for d in ctx.defs:
                if d in tuned:
                    continue
                for n in ast.walk(d):
                    if isinstance(n, ast.Call) \
                            and isinstance(n.func, ast.Name) \
                            and any(c in tuned
                                    for c in ctx.by_name.get(n.func.id,
                                                             ())):
                        tuned.add(d)
                        changed = True
                        break
        launches = set()
        for d in ctx.defs:
            if any(isinstance(n, ast.Call)
                   and (_dotted(n.func) or ())[-1:] == ("pallas_call",)
                   for n in ast.walk(d)):
                launches.add(d)
        # outermost launch defs only: a nested kernel closure belongs to
        # its enclosing launcher
        for d in launches:
            if any(a in launches for a in ctx.ancestors(d)):
                continue
            if d in tuned or any(a in tuned for a in ctx.ancestors(d)):
                continue
            emit("untuned-pallas-launch", d,
                 f"`{d.name}` contains a pl.pallas_call whose geometry "
                 "does not flow from the tuning-cache lookup helper "
                 "(paddle_tpu.tune.kernel_config) — hardcoded launch "
                 "geometry freezes one device's tradeoffs; resolve "
                 "block/grid choices through kernel_config")

    # ---- nondeterministic-sim (sim tier only) ----------------------------
    # The fleet simulator's hard invariant: virtual time + seeded
    # randomness, nothing else.  Same seed, same workload -> byte-
    # identical records; that is what makes sweep cells comparable and
    # regressions bisectable.  Any real-clock read or ambient-RNG call
    # in a sim/ directory quietly breaks it — flag them all.  Seeded
    # ``random.Random(seed)`` instances stay legal: the rule matches
    # the MODULE's global functions, not instance methods (an instance
    # call's dotted prefix is the variable name, never ``random``).
    if "sim" in re.split(r"[\\/]", path):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            dd = _dotted(node.func) or ()
            if not dd:
                continue
            how = None
            if dd[0] == "time" and dd[-1] in _WALL_CLOCK_FNS \
                    and len(dd) == 2:
                how = "a real-clock read"
            elif dd[-1] in ("now", "utcnow", "today") \
                    and any(p in ("datetime", "date") for p in dd[:-1]):
                how = "a wall-date read"
            elif len(dd) == 2 and dd[0] == "random" \
                    and dd[1] in _GLOBAL_RNG_FNS:
                how = "a global unseeded RNG call"
            elif len(dd) >= 3 and dd[0] in ctx.np_aliases \
                    and dd[1] == "random":
                how = "a global unseeded RNG call"
            if how is not None:
                emit("nondeterministic-sim", node,
                     f"`{'.'.join(dd)}()` is {how} inside the simulator "
                     "tier — the sim's hard invariant is virtual time "
                     "and seeded randomness (same seed -> byte-identical "
                     "records); thread a random.Random(seed) through and "
                     "advance time via the event loop")

    # ---- wallclock-in-timing-path (inference + profiler tiers) -----------
    # Timing contract: every duration in the serving and profiling tiers
    # comes from a monotonic clock — Tracer spans are perf_counter_ns,
    # ServingStats durations are perf_counter deltas, uptime is
    # monotonic().  A `time.time()` in these files measures the
    # NTP-adjustable wall clock: a slew mid-measurement makes the
    # duration jump or go negative, silently corrupting latency stats.
    if {"inference", "profiler"} & set(re.split(r"[\\/]", path)):
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) \
                    and _dotted(node.func) == ("time", "time"):
                emit("wallclock-in-timing-path", node,
                     "`time.time()` in a timing path — the wall clock is "
                     "not monotonic (NTP slew makes durations jump or go "
                     "negative); use time.perf_counter()/"
                     "perf_counter_ns(), or time.monotonic() for uptime")

    # ---- unbounded-observability-buffer (inference + profiler tiers) -----
    # Telemetry discipline: every always-on buffer in the observability
    # layer is bounded and counts what it sheds (the Tracer ring drops
    # and counts, the flight recorder LRU-evicts and counts, reservoirs
    # subsample).  An observability class that plain-appends per request
    # or per step is a slow leak on a long-running server.  Evidence of
    # a bound anywhere in the class acquits every append in it: a
    # capacity/maxlen/limit-named attribute, a deque(maxlen=...), or a
    # pop-style eviction call.
    if {"inference", "profiler"} & set(re.split(r"[\\/]", path)):
        obs_re = re.compile(r"Stats|Trace|Record|Flight|Window|Telemetry"
                            r"|SLO|Spool|Reservoir|Hist|Monitor|Detector"
                            r"|Ring")
        bound_re = re.compile(r"cap|maxlen|limit|max_|bound", re.IGNORECASE)
        for cls in ast.walk(ctx.tree):
            if not (isinstance(cls, ast.ClassDef)
                    and obs_re.search(cls.name)):
                continue
            bounded = False
            appends = []
            for node in ast.walk(cls):
                if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                    targets = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    for t in targets:
                        name = t.attr if isinstance(t, ast.Attribute) else (
                            t.id if isinstance(t, ast.Name) else "")
                        if name and bound_re.search(name):
                            bounded = True
                elif isinstance(node, ast.Call):
                    d = _dotted(node.func)
                    if d and d[-1] in ("pop", "popleft", "popitem"):
                        bounded = True
                    elif d and d[-1] == "deque" and any(
                            kw.arg == "maxlen" for kw in node.keywords):
                        bounded = True
                    elif d and d[-1] == "append":
                        appends.append(node)
                    if node.keywords and any(
                            kw.arg and bound_re.search(kw.arg)
                            for kw in node.keywords):
                        bounded = True
            if bounded:
                continue
            for node in appends:
                emit("unbounded-observability-buffer", node,
                     f"`.append` inside observability class `{cls.name}` "
                     "with no visible bound (no capacity/maxlen/limit "
                     "attribute, no deque(maxlen=), no pop-style "
                     "eviction) — always-on telemetry that grows per "
                     "request leaks on a long-running server; cap the "
                     "buffer and count what it sheds")
    return findings


# step/release/abort/recover paths: the functions whose failures the
# fault-tolerance machinery must be able to observe
_CRITICAL_RE = re.compile(r"step|release|abort|free|recover|retire",
                          re.IGNORECASE)
_LOG_FN_NAMES = {"debug", "info", "warning", "error", "exception", "log",
                 "print"}


def _broad_handler(handler: ast.ExceptHandler) -> bool:
    """Bare ``except:`` or a clause naming Exception/BaseException
    (directly or inside a tuple)."""
    t = handler.type
    if t is None:
        return True
    for n in (t.elts if isinstance(t, ast.Tuple) else [t]):
        d = _dotted(n)
        if d and d[-1] in ("Exception", "BaseException"):
            return True
    return False


def _swallowing_body(handler: ast.ExceptHandler) -> bool:
    """True when the handler body is pass/continue only, optionally after
    one logging call — i.e. the exception goes nowhere."""
    body = list(handler.body)
    if body and isinstance(body[0], ast.Expr) \
            and isinstance(body[0].value, ast.Call):
        func = body[0].value.func
        name = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else None)
        if name in _LOG_FN_NAMES:
            body = body[1:]
    if not body:
        return True                      # log-only handler
    return all(isinstance(s, (ast.Pass, ast.Continue)) for s in body)


def lint_file(path: str, root: str | None = None) -> list:
    with open(path, encoding="utf-8") as f:
        text = f.read()
    rel = os.path.relpath(path, root) if root else path
    try:
        return lint_source(text, rel)
    except SyntaxError as e:
        return [Finding("parse", ERROR, Location(rel, e.lineno or 0, ""),
                        f"syntax error: {e.msg}")]


def collect_py_files(paths) -> list:
    out = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [d for d in dirnames
                           if d != "__pycache__" and not d.startswith(".")]
            out.extend(os.path.join(dirpath, f) for f in sorted(filenames)
                       if f.endswith(".py"))
    return out


def lint_paths(paths, root: str | None = None) -> list:
    findings = []
    for f in collect_py_files(paths):
        findings.extend(lint_file(f, root=root))
    return findings
