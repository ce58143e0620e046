"""singalint rules — one per invariant PRs 1-4 established by hand.

| code   | name             | invariant                                      |
|--------|------------------|------------------------------------------------|
| SGL001 | jit-purity       | no host side effects reachable inside jax.jit  |
| SGL002 | donation-safety  | donated jit arguments are dead after the call  |
| SGL003 | recompile-hazard | no jax.jit in loops / .shape branching in jit  |
| SGL004 | (retired)        | thread-seam — folded into SGL010 (conc.py);    |
|        |                  | old disable=SGL004 suppressions fail loudly    |
| SGL005 | wall-clock       | time.time() is banned (monotonic-only rule)    |
| SGL006 | obs-kind         | record kinds are members of obs.schema._KINDS  |
| SGL007 | fault-site       | faults.fire/corrupt/tear sites are registered  |
| SGL008 | host-sync        | no device fetches in hot engine/runner loops   |
| SGL009 | flight-site      | flight-recorder dump sites are registered names|

Rules are module-local static analysis: each builds a one-level call
graph inside the file it lints (jit roots -> direct helper calls,
background entry points -> direct self-method calls) and never chases
imports — deep enough for every real seam in this codebase, shallow
enough to stay fast and predictable.  What a rule cannot see it does
not guess at: dynamic dispatch through variables, cross-module helpers
and exec'd code are out of scope by design (the dynamic checks —
tools/record_check.py, tools/ckpt_fsck.py, the chaos tests — cover the
runtime half).
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .framework import Finding, Rule, register

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

def _module_cache(tree: ast.AST) -> Dict[str, object]:
    """Per-parse memo attached to the Module node itself.

    Every rule needs the same module-level artifacts (node list, import
    map, parent links, def table, jit roots); without sharing, seven
    rules each re-walk the full tree and the repo-wide run costs ~8 s —
    past the tier-1 budget for the repo-is-clean gate.  Caching on the
    tree is safe because each ``lint_source`` call parses afresh."""
    cache = getattr(tree, "_singalint_cache", None)
    if cache is None:
        cache = {}
        tree._singalint_cache = cache  # type: ignore[attr-defined]
    return cache


def module_nodes(tree: ast.AST) -> List[ast.AST]:
    """Flat pre-order node list, walked once per parse."""
    cache = _module_cache(tree)
    if "nodes" not in cache:
        cache["nodes"] = list(ast.walk(tree))
    return cache["nodes"]  # type: ignore[return-value]


def module_calls(tree: ast.AST) -> List[ast.Call]:
    cache = _module_cache(tree)
    if "calls" not in cache:
        cache["calls"] = [n for n in module_nodes(tree)
                          if isinstance(n, ast.Call)]
    return cache["calls"]  # type: ignore[return-value]


def dotted_name(node: ast.AST) -> Optional[str]:
    """'self.pool.caches' for nested Attributes over a Name; None for
    anything involving calls/subscripts."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def build_parents(tree: ast.AST) -> Dict[ast.AST, ast.AST]:
    cache = _module_cache(tree)
    if "parents" not in cache:
        parents: Dict[ast.AST, ast.AST] = {}
        for node in module_nodes(tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node
        cache["parents"] = parents
    return cache["parents"]  # type: ignore[return-value]


def import_map(tree: ast.Module) -> Dict[str, str]:
    """local name -> canonical dotted path, relative dots stripped and a
    leading ``singa_tpu.`` normalized away (so ``from ..obs import
    events`` and ``from singa_tpu.obs import events`` both canonicalize
    to ``obs.events``)."""
    cache = _module_cache(tree)
    if "imports" in cache:
        return cache["imports"]  # type: ignore[return-value]
    mods: Dict[str, str] = {}

    def canon(path: str) -> str:
        path = path.lstrip(".")
        if path.startswith("singa_tpu."):
            path = path[len("singa_tpu."):]
        return path

    for node in module_nodes(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                local = a.asname or a.name.split(".")[0]
                mods[local] = canon(a.name if a.asname else
                                    a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            for a in node.names:
                if a.name == "*":
                    continue
                local = a.asname or a.name
                full = f"{base}.{a.name}" if base else a.name
                mods[local] = canon(full)
    cache["imports"] = mods
    return mods


def resolve(node: ast.AST, imports: Dict[str, str]) -> Optional[str]:
    """Canonical dotted path of an expression ('events.counter' ->
    'obs.events.counter'), or None when it is not a plain dotted name.

    The ``singa_tpu.`` prefix is stripped here as well as at
    import-statement time: ``import singa_tpu.obs.events`` leaves the
    local head as plain ``singa_tpu``, so the full attribute path only
    canonicalizes at use sites."""
    d = dotted_name(node)
    if d is None:
        return None
    head, _, rest = d.partition(".")
    base = imports.get(head, head)
    full = f"{base}.{rest}" if rest else base
    if full.startswith("singa_tpu."):
        full = full[len("singa_tpu."):]
    return full


def _is_jax_jit(call: ast.Call, imports: Dict[str, str]) -> bool:
    full = resolve(call.func, imports)
    if full == "jax.jit":
        return True
    # partial(jax.jit, static_argnums=...) used as a decorator factory
    if full in ("functools.partial", "partial") and call.args:
        return resolve(call.args[0], imports) == "jax.jit"
    return False


def _collect_defs(tree: ast.Module) -> Dict[str, List[ast.FunctionDef]]:
    cache = _module_cache(tree)
    if "defs" not in cache:
        defs: Dict[str, List[ast.FunctionDef]] = {}
        for node in module_nodes(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)
        cache["defs"] = defs
    return cache["defs"]  # type: ignore[return-value]


def _class_of(node: ast.AST,
              parents: Dict[ast.AST, ast.AST]) -> Optional[ast.ClassDef]:
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, ast.ClassDef):
            return cur
        cur = parents.get(cur)
    return None


def _methods(cls: ast.ClassDef) -> Dict[str, ast.FunctionDef]:
    return {n.name: n for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}


def _jit_roots(tree: ast.Module, imports: Dict[str, str],
               defs: Dict[str, List[ast.FunctionDef]]
               ) -> List[Tuple[ast.AST, ast.Call]]:
    """Functions (or lambdas) that end up wrapped by jax.jit in this
    module: decorated defs plus first arguments of jax.jit(...) calls."""
    cache = _module_cache(tree)
    if "jit_roots" in cache:
        return cache["jit_roots"]  # type: ignore[return-value]
    roots: List[Tuple[ast.AST, ast.Call]] = []
    seen: Set[int] = set()

    def add(fn: ast.AST, site: ast.Call) -> None:
        if id(fn) not in seen:
            seen.add(id(fn))
            roots.append((fn, site))

    for node in module_nodes(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if (resolve(dec, imports) == "jax.jit"
                        or (isinstance(dec, ast.Call)
                            and _is_jax_jit(dec, imports))):
                    add(node, dec if isinstance(dec, ast.Call) else None)
        elif isinstance(node, ast.Call) and node.args:
            # direct form jax.jit(fn, ...) or applied partial factory
            # partial(jax.jit, ...)(fn) — both wrap node.args[0]
            wraps = (resolve(node.func, imports) == "jax.jit"
                     or (isinstance(node.func, ast.Call)
                         and _is_jax_jit(node.func, imports)))
            if not wraps:
                continue
            target = node.args[0]
            if isinstance(target, ast.Lambda):
                add(target, node)
            else:
                name = dotted_name(target)
                if name and "." not in name and name in defs:
                    # nearest textually-preceding def wins (the common
                    # build-closure-then-jit pattern)
                    cands = [d for d in defs[name]
                             if d.lineno <= node.lineno]
                    if cands:
                        add(max(cands, key=lambda d: d.lineno), node)
    cache["jit_roots"] = roots
    return roots


def _reachable_in_jit(root: ast.AST, parents: Dict[ast.AST, ast.AST],
                      defs: Dict[str, List[ast.FunctionDef]]
                      ) -> List[ast.AST]:
    """The jitted function's own subtree plus ONE level of helpers it
    calls directly: locally-defined bare-name functions and same-class
    ``self.<method>()`` calls."""
    bodies: List[ast.AST] = [root]
    inside: Set[int] = {id(n) for n in ast.walk(root)}
    cls = _class_of(root, parents)
    methods = _methods(cls) if cls is not None else {}
    for node in ast.walk(root):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func)
        if name is None:
            continue
        helper: Optional[ast.AST] = None
        if "." not in name and name in defs:
            cands = [d for d in defs[name] if id(d) not in inside]
            if cands:
                helper = min(
                    cands, key=lambda d: abs(d.lineno - node.lineno))
        elif name.startswith("self.") and name.count(".") == 1:
            helper = methods.get(name.split(".", 1)[1])
        if helper is not None and id(helper) not in {id(b) for b in bodies}:
            bodies.append(helper)
    return bodies


# ---------------------------------------------------------------------------
# SGL001 jit-purity
# ---------------------------------------------------------------------------

#: module canonical-path prefixes whose calls are host side effects —
#: firing them under a jit trace means they run at TRACE time (once per
#: compile, silently skipped on cached executions), which is exactly
#: the bug class PR 4 pinned to "sites fire host-side OUTSIDE jit"
_IMPURE_MODULE_PREFIXES = ("obs.events.", "events.", "faults.",
                           "obs.record.", "record.")
_IMPURE_CALLS = {"time.time", "time.monotonic", "time.perf_counter",
                 "time.sleep", "print", "open", "input"}


@register
class JitPurityRule(Rule):
    code = "SGL001"
    name = "jit-purity"
    description = ("obs events, fault sites, print/file I/O and host "
                   "clocks must not be reachable inside jax.jit-wrapped "
                   "functions (one helper level followed)")

    def check(self, tree: ast.Module, src: str,
              path: str) -> Iterable[Finding]:
        imports = import_map(tree)
        defs = _collect_defs(tree)
        parents = build_parents(tree)
        reported: Set[Tuple[int, int]] = set()
        for root, _site in _jit_roots(tree, imports, defs):
            root_name = getattr(root, "name", "<lambda>")
            for body in _reachable_in_jit(root, parents, defs):
                for node in ast.walk(body):
                    if not isinstance(node, ast.Call):
                        continue
                    full = resolve(node.func, imports)
                    if full is None:
                        continue
                    # module prefixes only apply when the head is an
                    # actual import — a local variable that happens to
                    # be named `record`/`events` is not a side effect
                    head = (dotted_name(node.func) or "").partition(".")[0]
                    impure = (full in _IMPURE_CALLS
                              or (head in imports
                                  and any(full.startswith(p)
                                          for p in _IMPURE_MODULE_PREFIXES)))
                    key = (node.lineno, node.col_offset)
                    if impure and key not in reported:
                        reported.add(key)
                        shown = dotted_name(node.func) or full
                        yield self.finding(
                            path, node,
                            f"host side effect {shown}() reachable inside "
                            f"jit-wrapped {root_name!r}: it runs at trace "
                            f"time (once per compile), not per step — "
                            f"hoist it outside the jitted region")


# ---------------------------------------------------------------------------
# SGL002 donation-safety
# ---------------------------------------------------------------------------

def _donated_positions(call: ast.Call) -> List[int]:
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return [v.value]
        if isinstance(v, (ast.Tuple, ast.List)):
            out = []
            for e in v.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    out.append(e.value)
            return out
    return []


class _DonationScan:
    """Linear scan of one function body tracking donated-then-dead
    values.  Loops and branches are scanned in statement order (no
    back-edge analysis) — precise enough for the dispatch patterns this
    repo uses, and it never crosses function boundaries."""

    def __init__(self, rule: Rule, path: str,
                 registry: Dict[str, Tuple[List[int], int]]):
        self.rule = rule
        self.path = path
        self.registry = registry
        self.dead: Dict[str, int] = {}      # dotted name -> donation line
        self.findings: List[Finding] = []

    def scan_block(self, stmts: List[ast.stmt]) -> None:
        for stmt in stmts:
            self._scan_stmt(stmt)

    @staticmethod
    def _header_nodes(stmt: ast.stmt) -> List[ast.AST]:
        """The parts of a statement evaluated BEFORE its nested bodies —
        scanning the whole subtree of a compound statement and then
        recursing into its body would visit body expressions twice (and
        flag the donating call's own arguments as reads-after-donate)."""
        if isinstance(stmt, (ast.If, ast.While)):
            return [stmt.test]
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            return [stmt.iter, stmt.target]
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            out: List[ast.AST] = []
            for item in stmt.items:
                out.append(item.context_expr)
                if item.optional_vars is not None:
                    out.append(item.optional_vars)
            return out
        if isinstance(stmt, (ast.Try,)):
            return []
        return [stmt]                       # simple statement: whole node

    def _scan_stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return                          # separate scope
        header = self._header_nodes(stmt)

        def walk_header():
            for h in header:
                yield from ast.walk(h)

        # 1. loads already known dead -> findings
        if self.dead:
            for node in walk_header():
                if isinstance(node, (ast.Name, ast.Attribute)) and \
                        isinstance(getattr(node, "ctx", None), ast.Load):
                    d = dotted_name(node)
                    if d in self.dead:
                        self.findings.append(self.rule.finding(
                            self.path, node,
                            f"{d!r} was donated to a jitted call on line "
                            f"{self.dead[d]} (donate_argnums) and read "
                            f"afterwards — its buffer may already be "
                            f"aliased/overwritten; use the call's result "
                            f"or drop the donation"))
                        del self.dead[d]    # report once per donation
        # 2. donations made by this statement
        for node in walk_header():
            if isinstance(node, ast.Call):
                fname = dotted_name(node.func)
                entry = self.registry.get(fname) if fname else None
                if entry:
                    for pos in entry[0]:
                        if pos < len(node.args):
                            d = dotted_name(node.args[pos])
                            if d is not None:
                                self.dead[d] = node.lineno
        # 3. stores resurrect (reassignment means a fresh value)
        for node in walk_header():
            if isinstance(node, (ast.Name, ast.Attribute)) and \
                    isinstance(getattr(node, "ctx", None),
                               (ast.Store, ast.Del)):
                d = dotted_name(node)
                if d is not None:
                    for dead in [k for k in self.dead
                                 if k == d or k.startswith(d + ".")]:
                        del self.dead[dead]
        # 4. recurse into compound bodies in program order
        for field in ("body", "orelse", "finalbody"):
            sub = getattr(stmt, field, None)
            if isinstance(sub, list) and sub and \
                    isinstance(sub[0], ast.stmt):
                self.scan_block(sub)
        for handler in getattr(stmt, "handlers", []) or []:
            self.scan_block(handler.body)


@register
class DonationSafetyRule(Rule):
    code = "SGL002"
    name = "donation-safety"
    description = ("a value passed at a donate_argnums position must "
                   "not be read after the jitted call — the donated "
                   "buffer is dead")

    def check(self, tree: ast.Module, src: str,
              path: str) -> Iterable[Finding]:
        # pass 1: every `target = jax.jit(..., donate_argnums=...)`
        imports = import_map(tree)
        registry: Dict[str, Tuple[List[int], int]] = {}
        for node in module_nodes(tree):
            if not isinstance(node, ast.Assign) or \
                    not isinstance(node.value, ast.Call):
                continue
            call = node.value
            if resolve(call.func, imports) != "jax.jit":
                continue
            donated = _donated_positions(call)
            if not donated:
                continue
            for target in node.targets:
                d = dotted_name(target)
                if d is not None:
                    registry[d] = (donated, node.lineno)
        if not registry:
            return []
        # pass 2: linear read-after-donate scan of every function body
        findings: List[Finding] = []
        for node in module_nodes(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scan = _DonationScan(self, path, registry)
                scan.scan_block(node.body)
                findings.extend(scan.findings)
        return findings


# ---------------------------------------------------------------------------
# SGL003 recompile-hazard
# ---------------------------------------------------------------------------

@register
class RecompileHazardRule(Rule):
    code = "SGL003"
    name = "recompile-hazard"
    description = ("jax.jit inside a loop body builds a fresh executable "
                   "cache per iteration; branching on a traced "
                   "argument's .shape inside a jitted function forks the "
                   "compile cache per shape")

    def check(self, tree: ast.Module, src: str,
              path: str) -> Iterable[Finding]:
        imports = import_map(tree)
        defs = _collect_defs(tree)
        parents = build_parents(tree)
        # (a) jax.jit (or a partial(jax.jit, ...) factory) called
        # inside a for/while body
        for node in module_calls(tree):
            if _is_jax_jit(node, imports):
                cur = parents.get(node)
                while cur is not None and not isinstance(
                        cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Module)):
                    if isinstance(cur, (ast.For, ast.While)):
                        yield self.finding(
                            path, node,
                            "jax.jit(...) inside a loop body: every "
                            "iteration wraps a fresh callable, so the "
                            "jit cache never hits — hoist the jit out "
                            "of the loop")
                        break
                    cur = parents.get(cur)
        # (b) if <traced_arg>.shape inside a jitted function
        for root, _site in _jit_roots(tree, imports, defs):
            args = getattr(root, "args", None)
            if args is None:
                continue
            params = {a.arg for a in
                      list(args.posonlyargs) + list(args.args)
                      + list(args.kwonlyargs)} - {"self", "cls"}
            for node in ast.walk(root):
                test = node.test if isinstance(node, (ast.If, ast.IfExp)) \
                    else None
                if test is None:
                    continue
                for sub in ast.walk(test):
                    if isinstance(sub, ast.Attribute) and \
                            sub.attr == "shape":
                        base = dotted_name(sub.value)
                        if base and base.split(".")[0] in params:
                            yield self.finding(
                                path, sub,
                                f"Python branch on {base}.shape inside "
                                f"jit-wrapped "
                                f"{getattr(root, 'name', '<lambda>')!r}: "
                                f"each distinct shape traces a separate "
                                f"executable — make the branch static "
                                f"or move it outside jit")
                            break


# ---------------------------------------------------------------------------
# thread-seam helpers (shared with tools/lint/conc.py — the SGL004 rule
# itself is RETIRED: its check was subsumed by SGL010 conc-shared-state,
# which also covers executor/signal domains, a transitive self.* call
# closure, and unguarded reads paired with locked writes.  The guard
# recognizer below is the ONE implementation both eras share, so the
# recognition semantics could not drift across the migration.)
# ---------------------------------------------------------------------------

def _self_method(node: ast.AST) -> Optional[str]:
    d = dotted_name(node)
    if d and d.startswith("self.") and d.count(".") == 1:
        return d.split(".", 1)[1]
    return None


_GUARD_TOKENS = frozenset(
    {"lock", "rlock", "mutex", "mu", "cond", "condvar", "cv"})


def _is_guard_name(name: str) -> bool:
    """Whole-segment match: `self._lock`, `self.state_lock`,
    `self._rlock` guard; `self._clock` (contains 'lock') does not."""
    last = name.rsplit(".", 1)[-1].lower()
    return any(seg in _GUARD_TOKENS
               for seg in last.strip("_").split("_"))


def _lock_guarded(node: ast.AST, parents: Dict[ast.AST, ast.AST],
                  stop: ast.AST) -> bool:
    cur = parents.get(node)
    while cur is not None and cur is not stop:
        if isinstance(cur, ast.With):
            for item in cur.items:
                d = dotted_name(item.context_expr) or ""
                if d and _is_guard_name(d):
                    return True
        cur = parents.get(cur)
    return False


# ---------------------------------------------------------------------------
# SGL005 wall-clock
# ---------------------------------------------------------------------------

@register
class WallClockRule(Rule):
    code = "SGL005"
    name = "wall-clock"
    description = ("time.time() / datetime.now() / datetime.today() are "
                   "banned (monotonic-only rule): wall-clock jumps (NTP "
                   "step, suspend/resume) corrupt durations and "
                   "deadlines — use time.monotonic()/perf_counter(), or "
                   "suppress with a reason for genuine timestamps")

    #: wall-clock reads, post-``resolve()``: ``time.time`` plus the
    #: datetime spellings that hide the same jumpy clock behind an
    #: object (subtracting two ``datetime.now()`` results is the same
    #: NTP/suspend hazard as subtracting two ``time.time()`` results)
    _WALL_CLOCKS = {
        "time.time": "time.time()",
        "datetime.datetime.now": "datetime.now()",
        "datetime.datetime.today": "datetime.today()",
    }

    def check(self, tree: ast.Module, src: str,
              path: str) -> Iterable[Finding]:
        imports = import_map(tree)
        for node in module_calls(tree):
            spelled = self._WALL_CLOCKS.get(
                resolve(node.func, imports) or "")
            if spelled:
                yield self.finding(
                    path, node,
                    f"{spelled} reads the wall clock, which can jump "
                    f"(NTP, suspend/resume): use time.monotonic() for "
                    f"deadlines/durations or time.perf_counter() for "
                    f"timing; timestamps that must correlate across "
                    f"hosts are the one legitimate use — suppress with "
                    f"that reason")


# ---------------------------------------------------------------------------
# SGL006 obs-kind / SGL007 fault-site — literal-vs-registry checks
# ---------------------------------------------------------------------------

def _registry_literals(rel_path: str, var: str,
                       root: Optional[str] = None) -> Optional[Set[str]]:
    """String keys/members of a module-level literal assignment, parsed
    from source (the linter must not import singa_tpu — linting may run
    where jax cannot)."""
    path = os.path.join(root or _REPO_ROOT, rel_path)
    try:
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError):
        return None
    for node in tree.body:
        target = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            target = node.targets[0]
        elif isinstance(node, ast.AnnAssign):
            target = node.target
        if not (isinstance(target, ast.Name) and target.id == var):
            continue
        value = node.value
        out: Set[str] = set()
        if isinstance(value, ast.Dict):
            for k in value.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    out.add(k.value)
        elif isinstance(value, (ast.Tuple, ast.List, ast.Set)):
            for e in value.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    out.add(e.value)
        return out
    return None


_KINDS_CACHE: Dict[str, Optional[Set[str]]] = {}
_SITES_CACHE: Dict[str, Optional[Set[str]]] = {}
_INCIDENT_CACHE: Dict[str, Optional[Set[str]]] = {}


def _call_arg(call: ast.Call, idx: int, kwname: str) -> Optional[ast.AST]:
    """Positional argument ``idx``, or the ``kwname=`` keyword — the
    registry rules must see ``faults.fire(site=...)`` too."""
    if len(call.args) > idx:
        return call.args[idx]
    for kw in call.keywords:
        if kw.arg == kwname:
            return kw.value
    return None


def record_kinds(root: Optional[str] = None) -> Optional[Set[str]]:
    key = root or _REPO_ROOT
    if key not in _KINDS_CACHE:
        _KINDS_CACHE[key] = _registry_literals(
            os.path.join("singa_tpu", "obs", "schema.py"), "_KINDS", root)
    return _KINDS_CACHE[key]


def fault_sites(root: Optional[str] = None) -> Optional[Set[str]]:
    key = root or _REPO_ROOT
    if key not in _SITES_CACHE:
        _SITES_CACHE[key] = _registry_literals(
            os.path.join("singa_tpu", "faults", "sites.py"), "SITES", root)
    return _SITES_CACHE[key]


def incident_sites(root: Optional[str] = None) -> Optional[Set[str]]:
    """SITES ∪ INCIDENT_SITES — the names a flight-recorder dump (or an
    incident record) may carry; None when either registry is
    unloadable."""
    key = root or _REPO_ROOT
    if key not in _INCIDENT_CACHE:
        extra = _registry_literals(
            os.path.join("singa_tpu", "faults", "sites.py"),
            "INCIDENT_SITES", root)
        base = fault_sites(root)
        _INCIDENT_CACHE[key] = (None if base is None or extra is None
                                else base | extra)
    return _INCIDENT_CACHE[key]


@register
class ObsKindRule(Rule):
    code = "SGL006"
    name = "obs-kind"
    description = ("string literals passed as record kinds "
                   "(obs.record.new_entry) must be members of "
                   "obs.schema._KINDS — the static half of what "
                   "tools/record_check.py verifies dynamically")

    def check(self, tree: ast.Module, src: str,
              path: str) -> Iterable[Finding]:
        kinds = record_kinds()
        imports = import_map(tree)
        for node in module_calls(tree):
            full = resolve(node.func, imports) or ""
            if full.rsplit(".", 1)[-1] != "new_entry" or \
                    not ("record" in full or full == "new_entry"):
                continue
            kind = _call_arg(node, 0, "kind")
            if kind is None:
                continue
            if kinds is None:
                # self-disabling here would be a false clean: a renamed
                # or broken schema.py must fail the gate, not pass it
                yield self.finding(
                    path, node,
                    "cannot verify record kind: obs/schema.py _KINDS "
                    "registry could not be loaded — the schema file is "
                    "missing, renamed, or unparsable")
                continue
            if isinstance(kind, ast.Constant) and \
                    isinstance(kind.value, str) and kind.value not in kinds:
                yield self.finding(
                    path, kind,
                    f"record kind {kind.value!r} is not in "
                    f"obs.schema._KINDS ({', '.join(sorted(kinds))}) — "
                    f"register it in the schema (with payload "
                    f"validation) before emitting it")


@register
class FaultSiteRule(Rule):
    code = "SGL007"
    name = "fault-site"
    description = ("literal site names passed to faults.fire/"
                   "faults.corrupt/faults.tear must exist in "
                   "faults.sites.SITES — a typo'd site silently "
                   "injects nothing")

    def check(self, tree: ast.Module, src: str,
              path: str) -> Iterable[Finding]:
        sites = fault_sites()
        imports = import_map(tree)
        for node in module_calls(tree):
            full = resolve(node.func, imports) or ""
            if full not in ("faults.fire", "faults.corrupt",
                            "faults.tear"):
                continue
            site = _call_arg(node, 0, "site")
            if site is None:
                continue
            if sites is None:
                yield self.finding(
                    path, node,
                    "cannot verify fault site: faults/sites.py SITES "
                    "registry could not be loaded — the sites file is "
                    "missing, renamed, or unparsable")
                continue
            if isinstance(site, ast.Constant) and \
                    isinstance(site.value, str) and site.value not in sites:
                yield self.finding(
                    path, site,
                    f"fault site {site.value!r} is not registered in "
                    f"faults.sites.SITES ({', '.join(sorted(sites))}) — "
                    f"an unregistered site never fires; register it or "
                    f"fix the typo")


# ---------------------------------------------------------------------------
# SGL008 host-sync hazard
# ---------------------------------------------------------------------------

#: class-name suffixes whose step loops are "hot": one host sync per
#: tick serializes every dispatch behind a device round trip
_HOT_CLASS_SUFFIXES = ("Engine", "Runner")
#: hot entry points on those classes; the step region proper
_HOT_ROOT_NAMES = frozenset({"step", "run", "run_until_idle"})
#: canonical dotted paths that force a device->host transfer
_HOST_SYNC_CALLS = {"jax.device_get", "numpy.asarray", "numpy.array"}


@register
class HostSyncRule(Rule):
    code = "SGL008"
    name = "host-sync"
    description = ("device fetches (.item(), float(x), np.asarray, "
                   "jax.device_get) must not sit in hot engine/runner "
                   "loops (*Engine/*Runner step/run regions, one helper "
                   "level) — each one serializes the loop behind a "
                   "device round trip; suppress with the measured "
                   "justification when the fetch IS the product")

    def _hot_bodies(self, cls: ast.ClassDef):
        """(method name, body, how) for hot roots plus ONE level of
        ``self.helper()`` calls from them — the same reachability
        discipline as SGL004."""
        methods = _methods(cls)
        roots = {name: "hot entry point" for name in methods
                 if name in _HOT_ROOT_NAMES or name.startswith("_step")}
        reach = dict(roots)
        for name in list(roots):
            for node in ast.walk(methods[name]):
                if isinstance(node, ast.Call):
                    h = _self_method(node.func)
                    if h and h in methods and h not in reach:
                        reach[h] = f"called from {name}()"
        return [(name, methods[name], how) for name, how in reach.items()]

    def check(self, tree: ast.Module, src: str,
              path: str) -> Iterable[Finding]:
        imports = import_map(tree)
        for cls in [n for n in module_nodes(tree)
                    if isinstance(n, ast.ClassDef)
                    and n.name.endswith(_HOT_CLASS_SUFFIXES)]:
            for mname, body, how in self._hot_bodies(cls):
                for node in ast.walk(body):
                    if not isinstance(node, ast.Call):
                        continue
                    shown = None
                    full = resolve(node.func, imports)
                    if isinstance(node.func, ast.Attribute) and \
                            node.func.attr == "item" and not node.args:
                        shown = f"{dotted_name(node.func) or '.item'}()"
                    elif full in _HOST_SYNC_CALLS:
                        shown = f"{dotted_name(node.func) or full}()"
                    elif isinstance(node.func, ast.Name) and \
                            node.func.id == "float" and \
                            len(node.args) == 1 and isinstance(
                                node.args[0],
                                (ast.Name, ast.Attribute, ast.Subscript)):
                        shown = "float(...)"
                    if shown is None:
                        continue
                    yield self.finding(
                        path, node,
                        f"host-sync hazard: {shown} in "
                        f"{cls.name}.{mname}() ({how}) blocks on a "
                        f"device->host transfer inside the hot loop — "
                        f"keep values device-resident, batch the fetch, "
                        f"or suppress with the measured justification")


# ---------------------------------------------------------------------------
# SGL009 flight-site — registry check over flight-recorder dump calls
# ---------------------------------------------------------------------------

@register
class FlightSiteRule(Rule):
    code = "SGL009"
    name = "flight-site"
    description = ("literal site names passed to FlightRecorder dump "
                   "calls (obs.flight) must be registered fault sites "
                   "or faults.sites.INCIDENT_SITES members — a typo'd "
                   "dump site would silently never dump (the runtime "
                   "check only fires on the incident path itself)")

    @staticmethod
    def _is_dump_call(node: ast.Call, full: str) -> bool:
        """``obs.flight.dump(...)`` module-level calls, attribute calls
        on anything named like a flight recorder (``self.flight.dump``,
        ``self._flight.dump``), and flight-dump helper methods whose
        own name says both (``self._flight_dump(site, ...)`` — the
        form the engine/runner call with literal sites).  ``rec.dump``
        is NOT matched: something in the call must say 'flight'."""
        if full in ("obs.flight.dump", "flight.dump"):
            return True
        d = (dotted_name(node.func) or "").lower()
        if d.endswith(".dump") and "flight" in d:
            return True
        last = d.rsplit(".", 1)[-1]
        return "flight" in last and "dump" in last

    def check(self, tree: ast.Module, src: str,
              path: str) -> Iterable[Finding]:
        sites = incident_sites()
        imports = import_map(tree)
        for node in module_calls(tree):
            full = resolve(node.func, imports) or ""
            if not self._is_dump_call(node, full):
                continue
            site = _call_arg(node, 0, "site")
            if site is None:
                continue
            if sites is None:
                yield self.finding(
                    path, node,
                    "cannot verify flight-dump site: faults/sites.py "
                    "SITES/INCIDENT_SITES registries could not be "
                    "loaded — the sites file is missing, renamed, or "
                    "unparsable")
                continue
            if isinstance(site, ast.Constant) and \
                    isinstance(site.value, str) and site.value not in sites:
                yield self.finding(
                    path, site,
                    f"flight-dump site {site.value!r} is not a "
                    f"registered fault site or INCIDENT_SITES member "
                    f"({', '.join(sorted(sites))}) — an unregistered "
                    f"site raises at the worst possible moment (the "
                    f"incident) instead of dumping; register it or fix "
                    f"the typo")
