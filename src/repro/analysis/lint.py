"""AST-based repo-specific lint rules (RA001-RA011).

Generic linters cannot see this repo's contracts: that ``WorkerState``
mutations must go through the cache-invalidating property setters, that a
request's block hashes are memoized once and threaded as ``hashes=``
through every router/indexer hop, that jitted/Pallas functions must stay
pure and keep their grid-shaping arguments static, that the analytic
simulator runs on the event clock.  Each rule below encodes one such
contract; each is proven by a good/bad fixture pair under
``repro/analysis/fixtures/`` (``tests/test_analysis_rules.py``).

Suppression: a finding whose source line carries ``ra: allow[RA00x]``
(or ``ra: allow`` for any rule) is dropped — for tests that *deliberately*
violate a contract to prove the runtime sanitizer fires.  ``src/`` must
stay clean without suppressions (CI runs the pass with an empty
allowlist).
"""
from __future__ import annotations

import ast
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Set

# --------------------------------------------------------------- plumbing ---


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


@dataclass(frozen=True)
class Rule:
    code: str
    title: str
    doc: str
    scope: Callable[[str], bool]
    check: Callable[["Module"], Iterable[Finding]]


class Module:
    """One parsed file plus the lookups the rules share."""

    def __init__(self, path: str, source: str):
        self.path = path.replace("\\", "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        # module/class-level function defs by name (for resolving
        # ``jax.jit(fn)`` / ``pl.pallas_call(fn, ...)`` call targets)
        self.defs: Dict[str, ast.FunctionDef] = {
            n.name: n for n in ast.walk(self.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}

    def finding(self, rule: str, node: ast.AST, message: str) -> Finding:
        return Finding(rule, self.path, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0), message)

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return cur
            cur = self.parents.get(cur)
        return None


def dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` → "a.b.c"; None for anything not a pure name chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_self(node: ast.AST) -> bool:
    return isinstance(node, ast.Name) and node.id in ("self", "cls")


def _scope_all(path: str) -> bool:
    return True


def _scope_src(path: str) -> bool:
    return "src/repro/" in path or path.startswith("repro/")


def _scope_deterministic(path: str) -> bool:
    """Code the paper's numbers come from: src + benchmarks + examples
    (tests may use their own randomness, e.g. hypothesis)."""
    return (_scope_src(path) or "benchmarks/" in path
            or "examples/" in path)


# ------------------------------------------------------------------ RA001 ---

_SETTER_BACKED = ("_active_blocks", "_healthy", "_capacity")


def _check_ra001(m: Module) -> Iterable[Finding]:
    for node in ast.walk(m.tree):
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = list(node.targets)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        for tgt in targets:
            if (isinstance(tgt, ast.Attribute)
                    and tgt.attr in _SETTER_BACKED
                    and not _is_self(tgt.value)):
                yield m.finding(
                    "RA001", tgt,
                    f"direct write to `{tgt.attr}` bypasses the WorkerState "
                    f"property setter that invalidates the router's cached "
                    f"dense load vector; assign `{tgt.attr.lstrip('_')}` "
                    f"instead")


# ------------------------------------------------------------------ RA002 ---

_MEMO_METHODS = {"best_worker", "overlap_scores", "matched_blocks",
                 "on_schedule", "remove_worker_blocks", "select_worker"}
# `insert`/`route` are common names (list.insert, Flask-ish route);
# only count them against router/indexer/control-plane receivers.
_MEMO_METHODS_GUARDED = {"insert", "route"}
_MEMO_RECEIVERS = ("indexer", "router", "control")


def _binds_hashes(fn: ast.AST) -> bool:
    args = getattr(fn, "args", None)
    if args is not None:
        names = [a.arg for a in args.args + args.kwonlyargs
                 + args.posonlyargs]
        if "hashes" in names or "hs" in names:
            return True
    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)) and node is not fn:
            continue
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id in ("hashes", "hs"):
                    return True
        if isinstance(node, ast.Attribute) and node.attr == "hashes" \
                and isinstance(node.ctx, ast.Load):
            return True
    return False


def _check_ra002(m: Module) -> Iterable[Finding]:
    memo_fns: Dict[ast.AST, bool] = {}
    for node in ast.walk(m.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        name = node.func.attr
        if name in _MEMO_METHODS_GUARDED:
            recv = dotted(node.func.value) or ""
            if not any(r in recv for r in _MEMO_RECEIVERS):
                continue
        elif name not in _MEMO_METHODS:
            continue
        kw = {k.arg for k in node.keywords}
        if "hashes" in kw or None in kw:     # None == **kwargs passthrough
            continue
        fn = m.enclosing_function(node)
        if fn is None:
            continue
        if fn not in memo_fns:
            memo_fns[fn] = _binds_hashes(fn)
        if memo_fns[fn]:
            yield m.finding(
                "RA002", node,
                f"`{name}()` drops the per-request block-hash memo that is "
                f"in scope here; thread it through with `hashes=` so the "
                f"prompt is hashed once per request, not once per hop")


# ------------------------------------------------------------------ RA003 ---

_IMPURE_EXACT = {"time.time", "time.monotonic", "time.perf_counter",
                 "time.process_time", "time.sleep", "datetime.now",
                 "datetime.datetime.now", "os.urandom", "print", "input",
                 "id"}
_IMPURE_PREFIX = ("np.random.", "numpy.random.", "random.")
_MUTATORS = {"append", "extend", "add", "update", "pop", "popitem",
             "setdefault", "clear", "remove", "insert"}


def _jit_like(call_name: Optional[str]) -> bool:
    return call_name in ("jax.jit", "jit", "pjit", "jax.pjit")


def _pallas_like(call_name: Optional[str]) -> bool:
    return call_name is not None and (
        call_name.endswith("pallas_call") or call_name.endswith("_pallas"))


def _jitted_functions(m: Module) -> List[ast.AST]:
    """Functions that run under trace: jit-decorated defs, defs/lambdas
    passed to ``jax.jit``/``pl.pallas_call`` (incl. through
    ``functools.partial``)."""
    out: List[ast.AST] = []
    seen: Set[ast.AST] = set()

    def add(fn: Optional[ast.AST]) -> None:
        if fn is not None and fn not in seen:
            seen.add(fn)
            out.append(fn)

    def resolve(arg: ast.AST) -> Optional[ast.AST]:
        if isinstance(arg, ast.Lambda):
            return arg
        if isinstance(arg, ast.Name):
            return m.defs.get(arg.id)
        if isinstance(arg, ast.Call):        # functools.partial(fn, ...)
            name = dotted(arg.func)
            if name in ("functools.partial", "partial") and arg.args:
                return resolve(arg.args[0])
        return None

    for node in ast.walk(m.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                name = dotted(dec)
                if _jit_like(name):
                    add(node)
                elif isinstance(dec, ast.Call):
                    cname = dotted(dec.func)
                    if _jit_like(cname) or _pallas_like(cname):
                        add(node)
                    elif cname in ("functools.partial", "partial") \
                            and dec.args and _jit_like(dotted(dec.args[0])):
                        add(node)
        elif isinstance(node, ast.Call):
            cname = dotted(node.func)
            if (_jit_like(cname) or _pallas_like(cname)) and node.args:
                add(resolve(node.args[0]))
    return out


def _local_bindings(fn: ast.AST) -> Set[str]:
    bound: Set[str] = set()
    args = getattr(fn, "args", None)
    if args is not None:
        for a in (args.args + args.kwonlyargs + args.posonlyargs):
            bound.add(a.arg)
        if args.vararg:
            bound.add(args.vararg.arg)
        if args.kwarg:
            bound.add(args.kwarg.arg)
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            bound.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node is not fn:
            bound.add(node.name)
    return bound


def _check_ra003(m: Module) -> Iterable[Finding]:
    for fn in _jitted_functions(m):
        local = _local_bindings(fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = dotted(node.func)
            if name is not None and (
                    name in _IMPURE_EXACT
                    or any(name.startswith(p) for p in _IMPURE_PREFIX)):
                yield m.finding(
                    "RA003", node,
                    f"impure call `{name}()` inside a jit/Pallas-traced "
                    f"function: it runs once at trace time and its value is "
                    f"baked into the compiled computation")
                continue
            # container mutation: only bare statements (`xs.append(v)`) —
            # a consumed result (`a, b = opt.update(...)`) is a computation
            # on a module/object, not a side effect on a captured container
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _MUTATORS
                    and isinstance(m.parents.get(node), ast.Expr)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id not in local):
                yield m.finding(
                    "RA003", node,
                    f"mutation `{node.func.value.id}.{node.func.attr}(...)` "
                    f"of a captured container inside a jit/Pallas-traced "
                    f"function: side effects on captures happen at trace "
                    f"time only and silently diverge on cached executions")


# ------------------------------------------------------------------ RA004 ---

_KERNEL_SHAPING = {"blk_q", "blk_k", "blk", "block_q", "block_k",
                   "interpret", "causal", "grid"}


def _jit_static_names(dec: ast.AST) -> Optional[Set[str]]:
    """static_argnames of a jit decorator/call, or None if not jit-like."""
    if _jit_like(dotted(dec)):
        return set()
    if not isinstance(dec, ast.Call):
        return None
    cname = dotted(dec.func)
    is_partial_jit = (cname in ("functools.partial", "partial")
                      and dec.args and _jit_like(dotted(dec.args[0])))
    if not (_jit_like(cname) or is_partial_jit):
        return None
    statics: Set[str] = set()
    for kw in dec.keywords:
        if kw.arg == "static_argnames":
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    statics.add(el.value)
    return statics


def _calls_pallas(fn: ast.AST) -> bool:
    return any(isinstance(n, ast.Call) and _pallas_like(dotted(n.func))
               for n in ast.walk(fn))


def _check_ra004(m: Module) -> Iterable[Finding]:
    for node in ast.walk(m.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        statics: Optional[Set[str]] = None
        for dec in node.decorator_list:
            s = _jit_static_names(dec)
            if s is not None:
                statics = s
        if statics is None or not _calls_pallas(node):
            continue
        shaping = {a.arg for a in node.args.kwonlyargs} & _KERNEL_SHAPING
        missing = sorted(shaping - statics)
        if missing:
            yield m.finding(
                "RA004", node,
                f"jitted Pallas wrapper `{node.name}` takes kernel-shaping "
                f"kwarg(s) {missing} that are not in static_argnames: each "
                f"distinct value must recompile the kernel, and a traced "
                f"value would bake the first call's grid into every call")


# ------------------------------------------------------------------ RA005 ---

_NP_SAMPLERS = {"seed", "rand", "randn", "randint", "random", "choice",
                "shuffle", "permutation", "normal", "uniform", "poisson",
                "exponential", "lognormal", "standard_normal"}
_PY_SAMPLERS = {"random", "randint", "randrange", "choice", "choices",
                "shuffle", "sample", "uniform", "gauss", "betavariate",
                "seed"}


def _check_ra005(m: Module) -> Iterable[Finding]:
    for node in ast.walk(m.tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func)
        if name is None:
            continue
        if name in ("random.Random", "np.random.default_rng",
                    "numpy.random.default_rng") \
                and not node.args and not node.keywords:
            yield m.finding(
                "RA005", node,
                f"`{name}()` without a seed draws OS entropy: routing/"
                f"eviction decisions fed from it are unreproducible — pass "
                f"an explicit seed")
            continue
        parts = name.split(".")
        if len(parts) >= 3 and parts[-3] in ("np", "numpy") \
                and parts[-2] == "random" and parts[-1] in _NP_SAMPLERS:
            yield m.finding(
                "RA005", node,
                f"`{name}()` uses numpy's process-global RNG state; use a "
                f"seeded `np.random.default_rng(seed)` stream instead")
        elif len(parts) == 2 and parts[0] == "random" \
                and parts[1] in _PY_SAMPLERS:
            yield m.finding(
                "RA005", node,
                f"`{name}()` uses the process-global `random` module state; "
                f"use a seeded `random.Random(seed)` instance instead")


# ------------------------------------------------------------------ RA006 ---


def _is_set_expr(node: ast.AST) -> bool:
    if isinstance(node, (ast.Set, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        return dotted(node.func) in ("set", "frozenset")
    return False


def _check_ra006(m: Module) -> Iterable[Finding]:
    def hit(node: ast.AST) -> Finding:
        return m.finding(
            "RA006", node,
            "iterating a set: CPython set order is insertion-history- and "
            "hash-seed-dependent, so anything downstream (routing, "
            "eviction, event order) loses determinism — sort it first "
            "(`sorted(...)`)")

    for node in ast.walk(m.tree):
        if isinstance(node, (ast.For, ast.AsyncFor)) \
                and _is_set_expr(node.iter):
            yield hit(node.iter)
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            for gen in node.generators:
                if _is_set_expr(gen.iter):
                    yield hit(gen.iter)
        elif isinstance(node, ast.Call):
            name = dotted(node.func)
            if name in ("list", "tuple", "enumerate", "iter") and node.args \
                    and _is_set_expr(node.args[0]):
                yield hit(node.args[0])


# ------------------------------------------------------------------ RA007 ---

# Load-bearing private state and the one module allowed to touch it.
_PRIVATE_OWNERS = {
    "_state_cache": "core/router.py",       # router's dense load cache
    "_node_by_hash": "core/radix.py",       # radix lookup table
    "_worker_blocks": "core/radix.py",      # radix claim counters
    "_resident": "serving/engine.py",       # decode-worker residency LRU
    "_prefill": "serving/engine.py",        # jitted prompt pass
    "_resume": "serving/engine.py",         # jitted resume pass
    "_best_match": "serving/engine.py",     # prefix-cache walk (LRU-mutating)
    "_template_cache": "serving/simulator.py",
}


def _check_ra007(m: Module) -> Iterable[Finding]:
    for node in ast.walk(m.tree):
        if not isinstance(node, ast.Attribute):
            continue
        owner = _PRIVATE_OWNERS.get(node.attr)
        if owner is None or m.path.endswith(owner) or _is_self(node.value):
            continue
        yield m.finding(
            "RA007", node,
            f"`{node.attr}` is private coherence-critical state of "
            f"`repro/{owner.rsplit('.', 1)[0].replace('/', '.')}"
            f"{''}`; mutating or reading it cross-module bypasses the "
            f"invariants its owner maintains — use the public API")


# ------------------------------------------------------------------ RA008 ---


def _check_ra008(m: Module) -> Iterable[Finding]:
    pins: List[ast.Call] = []
    releases = 0
    for node in ast.walk(m.tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            if node.func.attr in ("pin", "admit_blocks"):
                pins.append(node)
            elif node.func.attr in ("unpin", "free"):
                releases += 1
    if pins and not releases:
        yield m.finding(
            "RA008", pins[0],
            "this module pins KV blocks (`pin`/`admit_blocks`) but never "
            "releases them (`unpin`/`free`): leaked pins make blocks "
            "permanently ineviction-proof and drive G1 into the "
            "over-subscribed regime for the wrong reason")


# ------------------------------------------------------------------ RA009 ---

# Modules that run on the simulated event clock (`now`), where a wall-clock
# read breaks replay determinism.
_EVENT_CLOCK_MODULES = (
    "serving/simulator.py", "serving/workload.py", "core/radix.py",
    "core/router.py", "core/kvbm.py", "core/poa.py", "core/saturation.py",
    "core/planner.py", "core/metrics.py", "core/games.py",
)

_WALL_CLOCK = {"time.time", "time.monotonic", "time.perf_counter",
               "time.process_time", "time.sleep", "datetime.now",
               "datetime.datetime.now"}


def _scope_event_clock(path: str) -> bool:
    return any(path.endswith(mod) for mod in _EVENT_CLOCK_MODULES)


def _check_ra009(m: Module) -> Iterable[Finding]:
    for node in ast.walk(m.tree):
        if isinstance(node, ast.Call) and dotted(node.func) in _WALL_CLOCK:
            yield m.finding(
                "RA009", node,
                f"wall-clock read `{dotted(node.func)}()` in an event-clock "
                f"module: the analytic plane is replay-deterministic only "
                f"if every timestamp derives from the simulated `now`")


# ------------------------------------------------------------------ RA010 ---


def _check_ra010(m: Module) -> Iterable[Finding]:
    for node in ast.walk(m.tree):
        if isinstance(node, ast.Call) \
                and (dotted(node.func) or "").endswith("pallas_call"):
            kw = {k.arg: k.value for k in node.keywords}
            val = kw.get("interpret")
            if val is None:
                yield m.finding(
                    "RA010", node,
                    "`pallas_call` without an `interpret=` kwarg: the kernel "
                    "silently falls back to compiled mode on CPU and fails "
                    "at lowering — thread the platform-derived flag through")
            elif isinstance(val, ast.Constant):
                yield m.finding(
                    "RA010", node,
                    f"`pallas_call(interpret={val.value!r})` hardcodes the "
                    f"execution mode: it must be threaded from the "
                    f"platform guard so TPU runs compiled and CPU runs "
                    f"interpret")
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            statics = None
            for dec in node.decorator_list:
                s = _jit_static_names(dec)
                if s is not None:
                    statics = s
            if statics is None:
                continue
            args = node.args
            kwonly = {a.arg: d for a, d in zip(args.kwonlyargs,
                                               args.kw_defaults)}
            dflt = kwonly.get("interpret")
            if dflt is not None and isinstance(dflt, ast.Constant) \
                    and dflt.value is not None:
                yield m.finding(
                    "RA010", node,
                    f"jitted kernel wrapper `{node.name}` defaults "
                    f"`interpret={dflt.value!r}`: default it to None and "
                    f"derive it from the lowering platform "
                    f"(`repro.kernels.dispatch.run_kernel`), so the "
                    f"CPU-interpret guard cannot be skipped by default")


# ------------------------------------------------------------------ RA011 ---

# Authoritative control-plane state a replica-side view may only read at
# sync time (ReplicaStateView.sync) — between syncs every read must come
# from the view's own frozen snapshot fields.
_AUTHORITATIVE_ATTRS = {"router", "indexer", "detector", "policy",
                        "workers", "dual", "planner", "poa"}
_RA011_CLASS_RE = None  # compiled lazily (re import kept local to the rule)


def _replica_view_class(name: str) -> bool:
    global _RA011_CLASS_RE
    if _RA011_CLASS_RE is None:
        import re
        _RA011_CLASS_RE = re.compile(r"^Replica\w*View$")
    return bool(_RA011_CLASS_RE.match(name))


def _enclosing_method_name(m: Module, node: ast.AST,
                           cls: ast.ClassDef) -> Optional[str]:
    cur = m.parents.get(node)
    name = None
    while cur is not None and cur is not cls:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            name = cur.name
        cur = m.parents.get(cur)
    return name


def _check_ra011(m: Module) -> Iterable[Finding]:
    for cls in ast.walk(m.tree):
        if not (isinstance(cls, ast.ClassDef)
                and _replica_view_class(cls.name)):
            continue
        for node in ast.walk(cls):
            if not isinstance(node, ast.Attribute):
                continue
            meth = _enclosing_method_name(m, node, cls)
            if meth == "sync":
                continue               # the one sanctioned authoritative read
            if node.attr == "_plane" and meth not in (None, "__init__"):
                yield m.finding(
                    "RA011", node,
                    f"replica view method `{meth}` reaches through "
                    f"`_plane` to live control-plane state: between syncs "
                    f"a replica may only read its own frozen snapshot "
                    f"fields (move the read into `sync()`)")
            elif node.attr in _AUTHORITATIVE_ATTRS \
                    and not _is_self(node.value):
                where = f"method `{meth}`" if meth else "class body"
                yield m.finding(
                    "RA011", node,
                    f"replica view {where} reads authoritative "
                    f"control-plane state `.{node.attr}` directly; "
                    f"replica-side code must route reads through the "
                    f"StateView snapshot (populate it in `sync()`)")


# ----------------------------------------------------------------- catalog --

RULES: List[Rule] = [
    Rule("RA001", "setter-bypassing WorkerState mutation",
         "Writes to `_active_blocks`/`_healthy`/`_capacity` on anything "
         "but `self` skip the property setters that invalidate the "
         "router's cached dense load vector — the router then routes on a "
         "stale view, which changes the measured game, not just speed.",
         _scope_all, _check_ra001),
    Rule("RA002", "dropped block-hash memo on a hot-path call",
         "Router/indexer entry points accept a `hashes=` memo so each "
         "request's chained block hashes are computed once.  A call that "
         "drops the memo while one is in scope silently re-hashes the "
         "prompt per hop (the pre-PR-4 hot-path regression).",
         _scope_src, _check_ra002),
    Rule("RA003", "impure capture inside a jit/Pallas-traced function",
         "Wall clocks, global RNG, `print`, and mutation of captured "
         "containers execute at trace time only: the first call's value "
         "is baked into the compiled artifact and later calls diverge "
         "without failing any test.",
         _scope_all, _check_ra003),
    Rule("RA004", "kernel-shaping kwargs missing from static_argnames",
         "`blk_*`/`interpret`/`causal` choose the Pallas grid; traced, "
         "they either crash at lowering or freeze the first call's grid "
         "into every subsequent call.",
         _scope_all, _check_ra004),
    Rule("RA005", "unseeded / process-global RNG",
         "Every stochastic choice that feeds routing, eviction, or "
         "workload sampling must come from an explicitly seeded stream; "
         "OS-entropy and process-global state make runs unreproducible "
         "and bit-exactness pins meaningless.",
         _scope_deterministic, _check_ra005),
    Rule("RA006", "iteration over an unordered set",
         "Set iteration order depends on insertion history and the "
         "per-process hash seed: any routing or eviction decision "
         "downstream of it is nondeterministic.  Sort before iterating.",
         _scope_src, _check_ra006),
    Rule("RA007", "cross-module access to coherence-critical private state",
         "`_state_cache`, `_node_by_hash`, `_worker_blocks`, the engine's "
         "jitted callables and caches: their owners maintain invariants "
         "on every mutation.  Touching them from another module bypasses "
         "those invariants (use the public API / audit hooks).",
         _scope_src, _check_ra007),
    Rule("RA008", "KV pins acquired but never released",
         "A module that pins blocks (`pin`/`admit_blocks`) without any "
         "release path (`unpin`/`free`) leaks refcounts: pinned blocks "
         "are eviction-proof, so the leak drives G1 over capacity "
         "permanently.",
         _scope_src, _check_ra008),
    Rule("RA009", "wall-clock read in an event-clock module",
         "The analytic simulator and the core game mechanisms run on the "
         "simulated clock; a `time.*` read there breaks replay "
         "determinism and couples results to host speed.",
         _scope_event_clock, _check_ra009),
    Rule("RA010", "Pallas interpret-mode guard missing or hardcoded",
         "Every `pallas_call` must thread a platform-derived `interpret` "
         "flag (compiled on TPU, interpret elsewhere); a hardcoded or "
         "missing flag either breaks CPU tests or silently runs "
         "interpret-mode on TPU.",
         _scope_all, _check_ra010),
    Rule("RA011", "replica-side read of authoritative control-plane state",
         "`Replica*View` classes are bounded-staleness snapshots: only "
         "`sync()` may read the plane's live router/indexer/detector "
         "state.  Any other method reaching through `_plane` (or stashing "
         "a live `.router`/`.indexer`/... reference) silently reintroduces "
         "fresh reads, and the measured staleness externality becomes a "
         "lie.",
         _scope_all, _check_ra011),
]

_RULES_BY_CODE = {r.code: r for r in RULES}


def rule_catalog() -> str:
    out = []
    for r in RULES:
        out.append(f"{r.code}  {r.title}")
        out.append(f"       {r.doc}")
    return "\n".join(out)


# ------------------------------------------------------------------ runner --

_ALLOW_TOKEN = "ra: allow"


def _suppressed(m: Module, f: Finding) -> bool:
    if not 1 <= f.line <= len(m.lines):
        return False
    line = m.lines[f.line - 1]
    idx = line.find(_ALLOW_TOKEN)
    if idx < 0:
        return False
    rest = line[idx + len(_ALLOW_TOKEN):]
    if not rest.lstrip().startswith("["):
        return True                                   # blanket allow
    codes = rest.lstrip()[1:].split("]", 1)[0]
    return f.rule in {c.strip() for c in codes.split(",")}


def lint_source(path: str, source: str,
                select: Optional[Sequence[str]] = None) -> List[Finding]:
    m = Module(path, source)
    findings: List[Finding] = []
    for rule in RULES:
        if select is not None and rule.code not in select:
            continue
        if not rule.scope(m.path):
            continue
        findings.extend(f for f in rule.check(m) if not _suppressed(m, f))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def lint_file(path, select: Optional[Sequence[str]] = None) -> List[Finding]:
    p = Path(path)
    return lint_source(str(p), p.read_text(), select=select)


_SKIP_DIRS = {"__pycache__", ".git", ".ruff_cache", "node_modules"}
# the lint pass never scans its own violation corpus
_FIXTURES = "repro/analysis/fixtures"


def iter_python_files(paths: Sequence[str]) -> List[Path]:
    out: List[Path] = []
    for root in paths:
        p = Path(root)
        if p.is_file() and p.suffix == ".py":
            out.append(p)
            continue
        for f in sorted(p.rglob("*.py")):
            rel = f.as_posix()
            if any(part in _SKIP_DIRS for part in f.parts):
                continue
            if _FIXTURES in rel:
                continue
            out.append(f)
    return out


def lint_paths(paths: Sequence[str],
               select: Optional[Sequence[str]] = None,
               allowlist: Sequence[str] = ()) -> List[Finding]:
    """Lint every .py file under ``paths``.  ``allowlist`` entries are
    ``"RULE path-substring"`` pairs (one per line in the CLI's
    ``--allowlist`` file); a matching finding is dropped."""
    allow = []
    for entry in allowlist:
        entry = entry.strip()
        if not entry or entry.startswith("#"):
            continue
        rule, _, frag = entry.partition(" ")
        allow.append((rule, frag.strip()))
    findings: List[Finding] = []
    for f in iter_python_files(paths):
        for fd in lint_file(f, select=select):
            if any(fd.rule == rule and frag and frag in fd.path
                   for rule, frag in allow):
                continue
            findings.append(fd)
    return findings
