"""Interprocedural analysis core for rtpu-check.

PR 4's rules are per-file and syntactic; the bug classes that still
bite under chaos — deadlocks from inconsistent lock order, leaked
pages/pins/leases on exception paths, non-idempotent retried RPCs —
all require *whole-program* reasoning.  This module provides the shared
substrate the interprocedural rules (``iparules.py``) consume:

* a **module graph** over ``ray_tpu/`` with import/alias resolution
  (``from x import f as g`` call sites resolve to ``x.f``, attribute
  receivers resolve through ``self.<attr> = Ctor(...)`` bindings);
* a **call graph**: ``self._method`` dispatch within a class and its
  bases, module-level functions, aliased cross-module calls, and
  constructor-typed attribute/local receivers (``self._kv.release`` →
  ``KVPageTable.release``);
* cached **per-function summaries**: locks acquired and held across
  calls, RPC call sites (with the retry/idempotent shape), blocking
  client entry points, self-attribute writes, append/increment-style
  mutations, and path-sensitive resource-lifecycle events;
* an **on-disk summary cache** keyed by file content hash, so a warm
  full-tree run and a ``--changed-only`` pre-commit run never re-parse
  unchanged modules.

Everything here is static (AST only) and runtime-import-free, same as
the rest of the analyzer.  Summaries are deliberately self-contained
plain data (JSON round-trippable): resolution that needs only
module-local knowledge (import aliases, attribute constructor types)
happens at summarize time; resolution that needs the whole tree (base
classes in other modules, dotted targets) happens at index time.
"""

from __future__ import annotations

import ast
import hashlib
import os
import pickle
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, \
    Sequence, Set, Tuple

__all__ = [
    "CACHE_VERSION", "FuncSummary", "ModuleSummary", "ProjectIndex",
    "ResourceSpec", "RESOURCE_SPECS", "SummaryCache", "default_cache_path",
    "module_dotted", "summarize_module",
]

#: bump when the summary format or the extraction logic changes — a
#: version mismatch invalidates the whole cache (content hashes only
#: catch *source* edits, not analyzer edits)
CACHE_VERSION = 9

#: client-API entry points that block the calling thread on runtime
#: RPC round trips (worker → raylet/GCS).  Holding a threading lock
#: across one serializes every other thread touching that lock behind
#: a network round trip (and the arena, and possibly a spill restore).
BLOCKING_CLIENT_CALLS = {
    "ray_tpu.get", "ray_tpu.put", "ray_tpu.wait", "ray_tpu.free",
}

_LOCK_KINDS = {"Lock": "lock", "RLock": "rlock", "Condition": "cond",
               "Semaphore": "sem", "BoundedSemaphore": "sem"}

#: list-shaped mutations that do NOT converge on replay (a retried
#: delivery double-applies); set.add/discard and keyed subscript
#: assignment converge and are deliberately absent
_BLIND_METHODS = {"append", "extend", "insert"}


# ---------------------------------------------------------------------------
# resource-lifecycle specs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ResourceSpec:
    """One acquire/release pairing checked path-sensitively.

    ``acquire_methods`` match ``<recv>.<m>(...)`` where the receiver's
    trailing symbol is in ``receiver_hints`` (empty = any receiver);
    ``acquire_funcs`` match alias-resolved dotted calls (``os.open``).
    ``key_arg`` names the argument that identifies the resource (the
    release must pass a textually matching expression); ``None`` means
    the *returned value* is the token (released via
    ``value.close()``-style ``release_value_methods`` or
    ``release_funcs(value)``).

    ``checked`` acquisitions return None/False on failure — the token
    only counts as held under a truthiness guard on the result.
    ``borrows`` are callables that may take the token as an argument
    without assuming ownership (``os.fstat(fd)`` reads the fd, it does
    not adopt it); any *other* call receiving the token is treated as
    an ownership escape.  ``strict_exceptions`` additionally requires
    the held region to be exception-safe: a statement that can raise
    while the token is held and unprotected (no enclosing
    try/finally/except releasing it) is a leak on the exception edge.
    """

    name: str
    acquire_methods: Tuple[str, ...] = ()
    receiver_hints: Tuple[str, ...] = ()
    acquire_funcs: Tuple[str, ...] = ()
    release_methods: Tuple[str, ...] = ()          # <recv>.<m>(key)
    release_value_methods: Tuple[str, ...] = ()    # token.<m>()
    release_funcs: Tuple[str, ...] = ()            # f(token)
    release_all_funcs: Tuple[str, ...] = ()        # releases every token
    key_arg: Optional[int] = None
    checked: bool = False
    borrows: Tuple[str, ...] = ()
    strict_exceptions: bool = False
    #: only functions that ALSO contain a release site are checked
    #: (for pairs whose acquire is legitimately open-ended elsewhere,
    #: e.g. failpoint arm helpers that tests disarm later)
    paired_only: bool = False
    hint: str = ""


#: the project's resource pairs (docs/static_analysis.md has the
#: registration walkthrough; tests retarget the engine at fixtures)
RESOURCE_SPECS: Tuple[ResourceSpec, ...] = (
    ResourceSpec(
        name="arena-pin",
        acquire_methods=("lease", "get_pinned"),
        receiver_hints=("store",),
        release_methods=("release",),
        key_arg=0,
        checked=True,
        borrows=("len", "bytes", "memoryview"),
        strict_exceptions=True,
        hint="every store.lease()/get_pinned() pin must reach "
             "store.release(oid) on all exits (the spill sweep treats "
             "a pinned object as in-use forever)"),
    ResourceSpec(
        name="spill-fd",
        acquire_funcs=("os.open",),
        release_funcs=("os.close",),
        release_value_methods=("close",),
        checked=False,
        borrows=("os.fstat", "os.pread", "os.read", "os.lseek",
                 "os.fdopen"),
        strict_exceptions=True,
        hint="a spill/restore fd that misses its os.close on an "
             "exception edge leaks until process exit (and on some "
             "tiers holds the blob's inode live)"),
    ResourceSpec(
        name="kv-page",
        acquire_methods=("reserve",),
        receiver_hints=("_kv", "kv", "kv_table", "table"),
        release_methods=("release",),
        key_arg=0,
        checked=True,
        hint="a KV page reservation must reach the release funnel "
             "(release(request_id)) or escape into the slot table; a "
             "dropped reservation strands budget until replica "
             "restart (allocated == freed + handed_off breaks)"),
    ResourceSpec(
        name="failpoint",
        acquire_funcs=("arm",),
        release_funcs=("disarm",),
        release_all_funcs=("disarm_all", "reload_env"),
        key_arg=0,
        paired_only=True,
        strict_exceptions=True,
        hint="a function that arms AND disarms a failpoint must "
             "disarm on the exception edge too (try/finally), or a "
             "failing run leaves the site armed for every later test"),
)


def _spec_fingerprint(specs: Sequence[ResourceSpec]) -> str:
    return hashlib.sha256(repr(tuple(specs)).encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# summaries
# ---------------------------------------------------------------------------

@dataclass
class FuncSummary:
    """One function's interprocedural facts.  All cross-references are
    module-local strings; the index resolves them globally."""

    qual: str                 # "Class.meth" or "func"
    cls: str                  # enclosing class name ("" = module level)
    name: str
    line: int
    is_async: bool = False
    #: locks this function itself acquires: (lockref, line, held-at)
    #: where lockref is "scope::sym" (scope = class name or "")
    acquires: List[Tuple[str, int, Tuple[str, ...]]] = \
        field(default_factory=list)
    #: call sites: (kind, a, b, line, locks-held) — kind/a/b encode the
    #: module-local callee reference (see _classify_call)
    calls: List[Tuple[str, str, str, int, Tuple[str, ...]]] = \
        field(default_factory=list)
    #: literal string args per call line (for wrapper-forward
    #: resolution): line -> (arg items "<idx>:<value>")
    call_lit_args: Dict[str, List[str]] = field(default_factory=dict)
    #: RPC sites: (method, kind, line, locks-held, idempotent) with
    #: kind in call|start_call|retry|client and idempotent in
    #: ""|"true"|"false" (the literal kwarg, when present)
    rpcs: List[Tuple[str, str, int, Tuple[str, ...], str]] = \
        field(default_factory=list)
    #: params (for retry-wrapper detection)
    params: Tuple[str, ...] = ()
    #: index of a param forwarded as call_with_retry's method (or -1)
    retry_forward_param: int = -1
    #: self attributes written (assign/del/subscript/mutating method)
    writes_attrs: Set[str] = field(default_factory=set)
    #: replay-divergent mutations: (attr, op, line) for blind
    #: list append/extend/insert and numeric += on self state
    blind_ops: List[Tuple[str, str, int]] = field(default_factory=list)
    #: function contains a keyed early-exit (an if whose test compares
    #: self state and whose body returns/raises) — the replay-guard
    #: shape a convergent handler uses to drop duplicate deliveries
    has_replay_guard: bool = False
    #: resource-lifecycle leak candidates found path-sensitively:
    #: (spec name, token, acquire line, leak line, kind) with kind in
    #: exit|exception|unassigned
    res_leaks: List[Tuple[str, str, int, int, str]] = \
        field(default_factory=list)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "q": self.qual, "c": self.cls, "n": self.name,
            "l": self.line, "a": int(self.is_async),
            "acq": [[r, ln, list(h)] for r, ln, h in self.acquires],
            "cal": [[k, x, y, ln, list(h)]
                    for k, x, y, ln, h in self.calls],
            "lit": self.call_lit_args,
            "rpc": [[m, k, ln, list(h), i]
                    for m, k, ln, h, i in self.rpcs],
            "par": list(self.params),
            "fwd": self.retry_forward_param,
            "wr": sorted(self.writes_attrs),
            "bl": [list(t) for t in self.blind_ops],
            "gd": int(self.has_replay_guard),
            "res": [list(t) for t in self.res_leaks],
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "FuncSummary":
        return cls(
            qual=d["q"], cls=d["c"], name=d["n"], line=d["l"],
            is_async=bool(d["a"]),
            acquires=[(r, ln, tuple(h)) for r, ln, h in d["acq"]],
            calls=[(k, x, y, ln, tuple(h))
                   for k, x, y, ln, h in d["cal"]],
            call_lit_args={k: list(v) for k, v in d["lit"].items()},
            rpcs=[(m, k, ln, tuple(h), i)
                  for m, k, ln, h, i in d["rpc"]],
            params=tuple(d["par"]),
            retry_forward_param=d["fwd"],
            writes_attrs=set(d["wr"]),
            blind_ops=[tuple(t) for t in d["bl"]],  # type: ignore[misc]
            has_replay_guard=bool(d["gd"]),
            res_leaks=[tuple(t) for t in d["res"]],  # type: ignore[misc]
        )


@dataclass
class ModuleSummary:
    path: str
    sha: str = ""
    dotted: str = ""
    #: import alias -> canonical dotted path
    aliases: Dict[str, str] = field(default_factory=dict)
    #: lockref ("scope::sym") -> {"kind", "alias_of"}
    lock_defs: Dict[str, Dict[str, str]] = field(default_factory=dict)
    #: class name -> {"bases": [dotted], "attrs": {attr: dotted target}}
    #: where an attr binding is "C:<dotted class>" (constructor type)
    #: or "F:<dotted func>" (callable binding)
    classes: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    #: qual -> FuncSummary
    functions: Dict[str, FuncSummary] = field(default_factory=dict)
    #: handle_* suffixes defined here (the whole-tree RPC registry)
    handlers: List[str] = field(default_factory=list)
    #: derived-signal names defined by RecordingRule(name=...) here
    signals: List[str] = field(default_factory=list)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "path": self.path, "sha": self.sha, "dotted": self.dotted,
            "aliases": self.aliases, "locks": self.lock_defs,
            "classes": self.classes,
            "functions": {q: f.to_dict()
                          for q, f in self.functions.items()},
            "handlers": self.handlers, "signals": self.signals,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ModuleSummary":
        return cls(
            path=d["path"], sha=d["sha"], dotted=d["dotted"],
            aliases=d["aliases"], lock_defs=d["locks"],
            classes=d["classes"],
            functions={q: FuncSummary.from_dict(f)
                       for q, f in d["functions"].items()},
            handlers=d["handlers"], signals=d["signals"],
        )


def module_dotted(path: str) -> str:
    """``ray_tpu/serve/kv_cache.py`` -> ``ray_tpu.serve.kv_cache``;
    package ``__init__.py`` maps to the package itself."""
    p = path[:-3] if path.endswith(".py") else path
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    return p.replace("/", ".")


def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _collect_aliases(tree: ast.Module) -> Dict[str, str]:
    aliases: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.asname:
                    aliases[alias.asname] = alias.name
                else:
                    top = alias.name.split(".")[0]
                    aliases[top] = top
        elif isinstance(node, ast.ImportFrom) and node.module:
            for alias in node.names:
                aliases[alias.asname or alias.name] = \
                    f"{node.module}.{alias.name}"
    return aliases


def _resolve_dotted(aliases: Dict[str, str], d: Optional[str]
                    ) -> Optional[str]:
    if d is None:
        return None
    head, _, rest = d.partition(".")
    canon = aliases.get(head)
    if canon is not None:
        return f"{canon}.{rest}" if rest else canon
    return d


def _str_arg(call: ast.Call, index: int) -> Optional[str]:
    if len(call.args) > index and isinstance(call.args[index], ast.Constant) \
            and isinstance(call.args[index].value, str):
        return call.args[index].value
    return None


def _self_attr(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        return node.attr
    return None


# ---------------------------------------------------------------------------
# summarize: module-level structure
# ---------------------------------------------------------------------------

def _lock_ctor_kind(aliases: Dict[str, str], value: ast.AST
                    ) -> Optional[Tuple[str, Optional[ast.Call]]]:
    """(kind, ctor call) when ``value`` constructs a threading lock."""
    if not isinstance(value, ast.Call):
        return None
    d = _resolve_dotted(aliases, _dotted(value.func))
    if d is None or not d.startswith("threading."):
        return None
    kind = _LOCK_KINDS.get(d.split(".")[-1])
    return (kind, value) if kind else None


def _collect_lock_defs(tree: ast.Module, aliases: Dict[str, str]
                       ) -> Dict[str, Dict[str, str]]:
    """lockref -> def.  Scope is the enclosing class for ``self.X``
    assignments, ``""`` for module/function-level names.  A
    ``Condition(existing_lock)`` aliases the wrapped lock — both names
    guard the same mutex, so holding one IS holding the other."""
    defs: Dict[str, Dict[str, str]] = {}

    def handle(node: ast.AST, scope: str) -> None:
        if not isinstance(node, (ast.Assign, ast.AnnAssign)):
            return
        value = node.value
        if value is None:
            return
        kc = _lock_ctor_kind(aliases, value)
        if kc is None:
            return
        kind, ctor = kc
        alias_of = ""
        if kind == "cond" and ctor is not None and ctor.args:
            wrapped = ctor.args[0]
            wsym = _self_attr(wrapped) or (
                wrapped.id if isinstance(wrapped, ast.Name) else None)
            if wsym:
                alias_of = f"{scope}::{wsym}"
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        for t in targets:
            sym = _self_attr(t) or (
                t.id if isinstance(t, ast.Name) else None)
            if sym:
                defs[f"{scope}::{sym}"] = {
                    "kind": kind, "alias_of": alias_of}

    # module-level names, then per-class self-attributes (the class
    # walk sees its methods' `self._lock = threading.Lock()` inits);
    # function-local locks are deliberately out of scope — they cannot
    # participate in a cross-function order cycle
    for node in tree.body:
        if isinstance(node, ast.ClassDef):
            for sub in ast.walk(node):
                handle(sub, node.name)
        else:
            handle(node, "")
    return defs


def _collect_attr_binds(cls_node: ast.ClassDef, aliases: Dict[str, str],
                        module_funcs: Set[str], dotted_mod: str
                        ) -> Dict[str, str]:
    """``self.<attr>`` bindings that type the receiver of later calls:
    ``self._kv = KVPageTable(...)`` binds ``_kv -> C:<dotted class>``;
    ``self._free = free or _default_free`` binds to the default
    callable (``F:<dotted func>``) — the common injectable-with-default
    pattern, where the default is what the tree actually runs."""
    binds: Dict[str, str] = {}

    def _callable_target(expr: ast.AST) -> Optional[str]:
        if isinstance(expr, ast.Call):
            d = _resolve_dotted(aliases, _dotted(expr.func))
            if d is None:
                return None
            if d.split(".")[-1][:1].isupper():
                return "C:" + (d if "." in d else f"{dotted_mod}.{d}")
            return None
        if isinstance(expr, ast.Name):
            if expr.id in module_funcs:
                return f"F:{dotted_mod}.{expr.id}"
            d = aliases.get(expr.id)
            if d is not None and "." in d:
                return f"F:{d}"
        return None

    for node in ast.walk(cls_node):
        if not isinstance(node, ast.Assign):
            continue
        attrs = [a for t in node.targets
                 if (a := _self_attr(t)) is not None]
        if not attrs:
            continue
        value = node.value
        candidates: List[ast.AST] = [value]
        if isinstance(value, ast.BoolOp):
            candidates = list(value.values)
        elif isinstance(value, ast.IfExp):
            candidates = [value.body, value.orelse]
        target = None
        for cand in candidates:
            target = _callable_target(cand)
            if target is not None:
                break
        if target is not None:
            for a in attrs:
                binds.setdefault(a, target)
    return binds


# ---------------------------------------------------------------------------
# summarize: per-function walk
# ---------------------------------------------------------------------------

def _rpc_site(call: ast.Call, aliases: Dict[str, str]
              ) -> Optional[Tuple[str, str]]:
    """(method, kind) for a literal-method RPC call site, or a blocking
    client entry point (kind='client', method=dotted name)."""
    method: Optional[str] = None
    kind = ""
    if isinstance(call.func, ast.Attribute):
        if call.func.attr == "call":
            method = _str_arg(call, 0) or _str_arg(call, 1)
            kind = "call"
        elif call.func.attr == "start_call":
            method = _str_arg(call, 0)
            kind = "start_call"
    d = _resolve_dotted(aliases, _dotted(call.func))
    if d is not None:
        tail = d.split(".")[-1]
        if tail == "call_with_retry":
            method = _str_arg(call, 1)
            kind = "retry"
        elif d in BLOCKING_CLIENT_CALLS:
            return d, "client"
    if method is None:
        return None
    return method, kind


def _idempotent_kw(call: ast.Call) -> str:
    for kw in call.keywords:
        if kw.arg == "idempotent" and isinstance(kw.value, ast.Constant):
            if kw.value.value is True:
                return "true"
            if kw.value.value is False:
                return "false"
    return ""


def _classify_call(call: ast.Call, aliases: Dict[str, str]
                   ) -> Optional[Tuple[str, str, str]]:
    """Module-local callee reference of one call site.

    Kinds: ``self`` (``self.m()``), ``attr`` (``self.<a>.m()``),
    ``local`` (``<var>.m()`` — resolved via local constructor types),
    ``dotted`` (alias-resolved dotted path, includes bare names).
    """
    func = call.func
    if isinstance(func, ast.Attribute):
        recv = func.value
        sa = _self_attr(func)
        if sa is not None:
            return "self", sa, ""
        inner = _self_attr(recv)
        if inner is not None:
            return "attr", inner, func.attr
        if isinstance(recv, ast.Name):
            # could be a local object or a module alias — record both
            # facets; the index tries local ctor types, then aliases
            return "local", recv.id, func.attr
        d = _resolve_dotted(aliases, _dotted(func))
        if d is not None:
            return "dotted", d, ""
        return None
    if isinstance(func, ast.Name):
        d = _resolve_dotted(aliases, _dotted(func))
        return "dotted", d or func.id, ""
    return None


class _FunctionWalker:
    """Sequential statement walk of one function body tracking the set
    of threading locks held at each call site (``with`` regions plus
    explicit acquire()/release() bracketing).  Nested function bodies
    are opaque — their statements run later, elsewhere."""

    def __init__(self, summary: FuncSummary, lock_defs: Dict[str, Dict],
                 aliases: Dict[str, str], cls: str):
        self.s = summary
        self.lock_defs = lock_defs
        self.aliases = aliases
        self.cls = cls
        self.held: List[str] = []

    # -- lock identity ----------------------------------------------------
    def _lockref(self, node: ast.AST) -> Optional[str]:
        """Resolve a with-item / acquire receiver to a lockref defined
        in this module (class scope first, then module scope)."""
        sym = _self_attr(node)
        if sym is not None:
            for scope in (self.cls, ""):
                ref = f"{scope}::{sym}"
                if ref in self.lock_defs:
                    return self._canon(ref)
            # self.X where X is a lock attr of ANOTHER class in this
            # module (mixin-style): match any class scope defining it
            for ref in self.lock_defs:
                if ref.endswith(f"::{sym}") and not ref.startswith("::"):
                    return self._canon(ref)
            return None
        if isinstance(node, ast.Name):
            ref = f"::{node.id}"
            return self._canon(ref) if ref in self.lock_defs else None
        return None

    def _canon(self, ref: str) -> str:
        seen = set()
        while ref in self.lock_defs and \
                self.lock_defs[ref].get("alias_of") and ref not in seen:
            seen.add(ref)
            nxt = self.lock_defs[ref]["alias_of"]
            if nxt not in self.lock_defs:
                break
            ref = nxt
        return ref

    # -- walk -------------------------------------------------------------
    def walk(self, body: List[ast.stmt]) -> None:
        for stmt in body:
            self._stmt(stmt)

    def _stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            pushed = 0
            for item in stmt.items:
                expr = item.context_expr
                recv = expr
                if isinstance(expr, ast.Call):
                    self._exprs(expr)
                    if isinstance(expr.func, ast.Attribute):
                        recv = expr.func.value
                ref = self._lockref(recv)
                if ref is not None:
                    self.s.acquires.append(
                        (ref, stmt.lineno, tuple(self.held)))
                    self.held.append(ref)
                    pushed += 1
            for sub in stmt.body:
                self._stmt(sub)
            for _ in range(pushed):
                self.held.pop()
            return
        if isinstance(stmt, (ast.If, ast.While)):
            self._exprs(stmt.test)
            for sub in stmt.body:
                self._stmt(sub)
            for sub in stmt.orelse:
                self._stmt(sub)
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._exprs(stmt.iter)
            for sub in stmt.body:
                self._stmt(sub)
            for sub in stmt.orelse:
                self._stmt(sub)
            return
        if isinstance(stmt, ast.Try):
            for sub in stmt.body:
                self._stmt(sub)
            for handler in stmt.handlers:
                for sub in handler.body:
                    self._stmt(sub)
            for sub in stmt.orelse:
                self._stmt(sub)
            for sub in stmt.finalbody:
                self._stmt(sub)
            return
        # explicit acquire()/release() bracketing (sequential)
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Call):
            call = stmt.value
            if isinstance(call.func, ast.Attribute) \
                    and call.func.attr in ("acquire", "release"):
                ref = self._lockref(call.func.value)
                if ref is not None:
                    if call.func.attr == "acquire":
                        self.s.acquires.append(
                            (ref, stmt.lineno, tuple(self.held)))
                        self.held.append(ref)
                    elif ref in self.held:
                        self.held.remove(ref)
                    return
        self._exprs(stmt)

    def _exprs(self, node: ast.AST) -> None:
        """Record every call in ``node`` with the current held set."""
        for sub in ast.walk(node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if not isinstance(sub, ast.Call):
                continue
            held = tuple(self.held)
            rpc = _rpc_site(sub, self.aliases)
            if rpc is not None:
                method, kind = rpc
                self.s.rpcs.append((method, kind, sub.lineno, held,
                                    _idempotent_kw(sub)))
            ref = _classify_call(sub, self.aliases)
            if ref is not None:
                kind, a, b = ref
                self.s.calls.append((kind, a, b, sub.lineno, held))
                lits = [f"{i}:{v.value}"
                        for i, v in enumerate(sub.args[:4])
                        if isinstance(v, ast.Constant)
                        and isinstance(v.value, str)]
                if lits:
                    self.s.call_lit_args.setdefault(
                        str(sub.lineno), []).extend(lits)


# -- retry/persist facts ----------------------------------------------------

class _StateFactsVisitor(ast.NodeVisitor):
    """Self-state writes, blind (replay-divergent) mutations, and the
    replay-guard shape, for the retry-safety rule."""

    def __init__(self, summary: FuncSummary):
        self.s = summary
        #: local name -> self attr it was derived from
        #: (``cur = self._metrics.get(key)`` — a later ``cur[...] +=``
        #: accumulates into that table through the local)
        self._derived: Dict[str, str] = {}

    def visit_FunctionDef(self, node):  # nested defs are opaque
        pass

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    @staticmethod
    def _rooted_attr(node: ast.AST) -> Optional[str]:
        while isinstance(node, ast.Subscript):
            node = node.value
        return _self_attr(node)

    def _derived_attr(self, node: ast.AST) -> Optional[str]:
        while isinstance(node, ast.Subscript):
            node = node.value
        if isinstance(node, ast.Name):
            return self._derived.get(node.id)
        return None

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            attr = self._rooted_attr(t)
            if attr is not None:
                self.s.writes_attrs.add(attr)
            if isinstance(t, ast.Name) and isinstance(node.value, ast.Call) \
                    and isinstance(node.value.func, ast.Attribute) \
                    and node.value.func.attr in ("get", "setdefault"):
                src = self._rooted_attr(node.value.func.value)
                if src is not None:
                    self._derived[t.id] = src
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        attr = self._rooted_attr(node.target)
        derived = self._derived_attr(node.target)
        if attr is not None:
            self.s.writes_attrs.add(attr)
        if isinstance(node.op, (ast.Add, ast.Sub)):
            which = attr or derived
            if which is not None:
                self.s.blind_ops.append((which, "aug", node.lineno))
        self.generic_visit(node)

    def visit_Delete(self, node: ast.Delete) -> None:
        for t in node.targets:
            attr = self._rooted_attr(t)
            if attr is not None:
                self.s.writes_attrs.add(attr)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        if isinstance(node.func, ast.Attribute):
            attr = self._rooted_attr(node.func.value) \
                or self._derived_attr(node.func.value)
            m = node.func.attr
            if attr is not None:
                if m in ("pop", "popitem", "update", "clear", "add",
                         "discard", "remove", "setdefault",
                         *_BLIND_METHODS):
                    self.s.writes_attrs.add(attr)
                if m in _BLIND_METHODS:
                    self.s.blind_ops.append((attr, m, node.lineno))
        self.generic_visit(node)

    def visit_If(self, node: ast.If) -> None:
        # replay-guard shape: `if <compare involving self state>:
        #     return/raise/continue` — the keyed early exit a
        # convergent handler uses to drop an already-applied delivery
        if not self.s.has_replay_guard:
            test_touches_self = any(
                _self_attr(sub) is not None
                or (isinstance(sub, ast.Name) and sub.id in self._derived)
                for sub in ast.walk(node.test))
            has_cmp = any(isinstance(sub, ast.Compare)
                          for sub in ast.walk(node.test))
            exits = any(isinstance(s, (ast.Return, ast.Raise,
                                       ast.Continue))
                        for s in node.body)
            if test_touches_self and has_cmp and exits:
                self.s.has_replay_guard = True
        self.generic_visit(node)


def _detect_retry_forward(fn: ast.AST, summary: FuncSummary,
                          aliases: Dict[str, str]) -> None:
    """A wrapper whose body forwards one of its params as
    ``call_with_retry``'s method arg (``def _gcs_call_retry(self,
    method, data)``) makes every literal-method call site of the
    wrapper a retrying call path."""
    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node is not fn:
            continue
        if not isinstance(node, ast.Call):
            continue
        d = _resolve_dotted(aliases, _dotted(node.func))
        if d is None or d.split(".")[-1] != "call_with_retry":
            continue
        if len(node.args) > 1 and isinstance(node.args[1], ast.Name):
            name = node.args[1].id
            if name in summary.params:
                summary.retry_forward_param = summary.params.index(name)
                return


# ---------------------------------------------------------------------------
# summarize: path-sensitive resource lifecycle
# ---------------------------------------------------------------------------

class _Token:
    __slots__ = ("spec", "key", "line", "state", "protected", "alt")

    def __init__(self, spec: ResourceSpec, key: str, line: int,
                 alt: Optional[str] = None):
        self.spec = spec
        self.key = key          # var name or key-arg source text
        self.alt = alt          # bound result variable, when distinct
        self.line = line
        self.state = "held"     # held | released | escaped
        self.protected = False  # a finally/handler releases this spec

    def names(self) -> Set[str]:
        """Every name this token answers to: the key expression, its
        base, and the variable the acquire's result was bound to —
        ``lease = store.lease(oid)`` is released by key
        (``release(oid)``) but guarded/escaped by result
        (``if lease is None`` / ``out[k] = lease``)."""
        out = {self.key, self.key.split(".")[0].split("[")[0]}
        if self.alt:
            out.add(self.alt)
            out.add(self.alt.split(".")[0].split("[")[0])
        return out

    def key_matches(self, key: Optional[str]) -> bool:
        if key is None:
            return True
        if key in self.names():
            return True
        return key.endswith(self.key) or self.key.endswith(key)


def _expr_src(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - unparse fallback
        return "<expr>"


def _base_name(node: ast.AST) -> Optional[str]:
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


class _ResourceScanner:
    """Structured abstract interpretation of one function body for one
    set of resource specs.  Tracks acquisitions to their release /
    ownership escape; reports a leak when a path exits (return, fall
    off the end, explicit raise) with a live token, and — for
    strict-exception specs — when a raising statement sits in the held
    region with no protecting finally/handler."""

    def __init__(self, summary: FuncSummary, aliases: Dict[str, str],
                 specs: Sequence[ResourceSpec]):
        self.s = summary
        self.aliases = aliases
        self.specs = specs
        self.tokens: List[_Token] = []
        #: specs released in an enclosing finally/except (stack depth)
        self._protect: List[Set[str]] = []

    # -- site matching ----------------------------------------------------
    def _acquire_of(self, call: ast.Call) -> Optional[ResourceSpec]:
        if isinstance(call.func, ast.Attribute):
            m = call.func.attr
            recv_sym = _self_attr(call.func.value) or (
                call.func.value.id
                if isinstance(call.func.value, ast.Name) else
                call.func.value.attr
                if isinstance(call.func.value, ast.Attribute) else None)
            for spec in self.specs:
                if m in spec.acquire_methods and (
                        not spec.receiver_hints
                        or recv_sym in spec.receiver_hints):
                    return spec
        d = _resolve_dotted(self.aliases, _dotted(call.func))
        if d is not None:
            tail = d.split(".")[-1]
            for spec in self.specs:
                if d in spec.acquire_funcs or tail in spec.acquire_funcs:
                    return spec
        return None

    def _match_release(self, call: ast.Call) -> Optional[Tuple[
            ResourceSpec, Optional[str], bool]]:
        """(spec, key-or-None, release_all) when ``call`` is a release
        site of one of our specs."""
        if isinstance(call.func, ast.Attribute):
            m = call.func.attr
            recv = call.func.value
            for spec in self.specs:
                if m in spec.release_methods:
                    key = _expr_src(call.args[0]) if call.args else None
                    return spec, key, False
                if m in spec.release_value_methods:
                    return spec, _expr_src(recv), False
        d = _resolve_dotted(self.aliases, _dotted(call.func))
        if d is not None:
            tail = d.split(".")[-1]
            for spec in self.specs:
                if d in spec.release_funcs or tail in spec.release_funcs:
                    key = _expr_src(call.args[0]) if call.args else None
                    return spec, key, False
                if d in spec.release_all_funcs \
                        or tail in spec.release_all_funcs:
                    return spec, None, True
        return None

    def _is_borrow(self, spec: ResourceSpec, call: ast.Call) -> bool:
        d = _resolve_dotted(self.aliases, _dotted(call.func))
        if d is None:
            return False
        tail = d.split(".")[-1]
        return d in spec.borrows or tail in spec.borrows

    # -- token ops --------------------------------------------------------
    def _live(self) -> List[_Token]:
        return [t for t in self.tokens if t.state == "held"]

    def _release(self, spec: ResourceSpec, key: Optional[str],
                 release_all: bool) -> None:
        for t in self.tokens:
            if t.spec.name != spec.name or t.state != "held":
                continue
            if release_all or t.key_matches(key):
                t.state = "released"

    def _escape_names(self, node: ast.AST) -> None:
        """Any live token whose name (key, key base, or bound result)
        flows into ``node`` — stored, returned, yielded, or passed to
        a non-borrow call — escapes ownership."""
        names: Set[str] = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name):
                names.add(sub.id)
        if not names:
            return
        for t in self._live():
            if t.names() & names:
                t.state = "escaped"

    def _call_args_escape(self, call: ast.Call) -> None:
        rel = self._match_release(call)
        for t in self._live():
            if rel is not None and rel[0].name == t.spec.name:
                continue
            if self._is_borrow(t.spec, call):
                continue
            tnames = t.names()
            for arg in list(call.args) + [kw.value for kw in call.keywords]:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Name) and sub.id in tnames:
                        t.state = "escaped"
                        break

    def _handle_calls(self, node: ast.AST) -> None:
        """Releases and argument-escapes for every call in ``node``."""
        for sub in ast.walk(node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if isinstance(sub, ast.Call):
                rel = self._match_release(sub)
                if rel is not None:
                    self._release(*rel)
                self._call_args_escape(sub)

    #: callee tails that do not raise in practice — container access,
    #: id formatting, clock reads, logging.  Without this, every
    #: ``conn.context.setdefault(...)`` between an acquire and its
    #: escape is an "exception edge" and the strict specs drown in
    #: noise.  An await or any other call still counts as raising.
    _SAFE_CALLEE_TAILS = frozenset({
        "get", "setdefault", "pop", "add", "discard", "append",
        "items", "keys", "values", "copy", "update", "len",
        "hex", "binary", "monotonic", "time", "isinstance",
        "debug", "info", "warning", "error", "exception",
        # container constructors (empty or copying a known container)
        "set", "dict", "list", "tuple", "frozenset",
    })

    @classmethod
    def _can_raise(cls, stmt: ast.stmt) -> bool:
        for sub in ast.walk(stmt):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if isinstance(sub, (ast.Await, ast.Raise)):
                return True
            if isinstance(sub, ast.Call):
                d = _dotted(sub.func)
                tail = d.split(".")[-1] if d else ""
                if tail not in cls._SAFE_CALLEE_TAILS:
                    return True
        return False

    def _leak(self, t: _Token, line: int, kind: str) -> None:
        t.state = "escaped"  # report once per acquisition
        self.s.res_leaks.append((t.spec.name, t.key, t.line, line, kind))

    # -- statement walk ---------------------------------------------------
    def walk(self, body: List[ast.stmt], end_line: int) -> None:
        self._stmts(body)
        for t in self._live():
            self._leak(t, end_line, "exit")

    def _stmts(self, body: List[ast.stmt]) -> None:
        for stmt in body:
            self._stmt(stmt)

    def _acquire_in(self, node: ast.AST
                    ) -> Optional[Tuple[ResourceSpec, ast.Call]]:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                spec = self._acquire_of(sub)
                if spec is not None:
                    return spec, sub
        return None

    def _protected(self, spec: ResourceSpec) -> bool:
        return any(spec.name in s for s in self._protect)

    def _stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return

        # strict-exception check BEFORE interpreting the statement: a
        # raising statement while a token is held and unprotected is an
        # exception-edge leak (the acquire statement itself is exempt)
        if self._can_raise(stmt) and not isinstance(stmt, ast.Raise):
            for t in self._live():
                if t.spec.strict_exceptions and not t.protected \
                        and not self._protected(t.spec) \
                        and stmt.lineno > t.line:
                    # the statement that releases/escapes this very
                    # token is not an exception hazard for it — probe
                    # on a copy of the interpretation
                    if self._stmt_settles(stmt, t):
                        continue
                    self._leak(t, stmt.lineno, "exception")

        if isinstance(stmt, (ast.Assign, ast.AnnAssign)):
            value = stmt.value
            acq = self._acquire_in(stmt) if value is not None else None
            self._handle_calls(stmt)
            if acq is not None:
                spec, call = acq
                targets = stmt.targets if isinstance(stmt, ast.Assign) \
                    else [stmt.target]
                key = None
                alt = None
                if targets and isinstance(targets[0],
                                          (ast.Name, ast.Attribute)):
                    alt = _expr_src(targets[0])
                if spec.key_arg is not None \
                        and len(call.args) > spec.key_arg:
                    key = _expr_src(call.args[spec.key_arg])
                elif alt is not None:
                    key, alt = alt, None
                if key is not None:
                    tok = _Token(spec, key, stmt.lineno, alt=alt)
                    tok.protected = self._protected(spec)
                    self.tokens.append(tok)
                    if spec.key_arg is None and not isinstance(
                            targets[0], ast.Name):
                        tok.state = "escaped"  # stored straight away
            else:
                # a live token stored into a container/attribute is an
                # ownership escape (released elsewhere, by the owner)
                if isinstance(stmt, ast.Assign):
                    for t_node in stmt.targets:
                        if isinstance(t_node, (ast.Attribute,
                                               ast.Subscript)):
                            if stmt.value is not None:
                                self._escape_names(stmt.value)
            return

        if isinstance(stmt, ast.Expr):
            acq = self._acquire_in(stmt)
            self._handle_calls(stmt)
            if acq is not None:
                spec, call = acq
                if spec.key_arg is not None \
                        and len(call.args) > spec.key_arg:
                    tok = _Token(spec, _expr_src(call.args[spec.key_arg]),
                                 stmt.lineno)
                    tok.protected = self._protected(spec)
                    self.tokens.append(tok)
                elif not spec.checked:
                    # unassigned value-token acquire: nothing can ever
                    # release it — immediate leak
                    self.s.res_leaks.append(
                        (spec.name, "<unassigned>", stmt.lineno,
                         stmt.lineno, "unassigned"))
            return

        if isinstance(stmt, ast.Return):
            if stmt.value is not None:
                self._handle_calls(stmt.value)
                self._escape_names(stmt.value)
            for t in self._live():
                if not t.protected and not self._protected(t.spec):
                    self._leak(t, stmt.lineno, "exit")
            return

        if isinstance(stmt, ast.Raise):
            if stmt.exc is not None:
                self._handle_calls(stmt.exc)
            for t in self._live():
                if t.spec.strict_exceptions and not t.protected \
                        and not self._protected(t.spec):
                    self._leak(t, stmt.lineno, "exception")
            return

        if isinstance(stmt, ast.If):
            self._branch_if(stmt)
            return

        if isinstance(stmt, (ast.While, ast.For, ast.AsyncFor)):
            if isinstance(stmt, (ast.For, ast.AsyncFor)):
                self._handle_calls(stmt.iter)
            else:
                self._handle_calls(stmt.test)
            self._stmts(stmt.body)
            self._stmts(stmt.orelse)
            return

        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                if isinstance(item.context_expr, ast.Call):
                    spec = self._acquire_of(item.context_expr)
                    if spec is not None:
                        continue  # context manager releases it
                self._handle_calls(item.context_expr)
            self._stmts(stmt.body)
            return

        if isinstance(stmt, ast.Try):
            # which specs does a finally/handler release?  tokens held
            # through the body are protected for those specs
            protected: Set[str] = set()
            for blk in [stmt.finalbody] + [h.body for h in stmt.handlers]:
                for sub_stmt in blk:
                    for sub in ast.walk(sub_stmt):
                        if isinstance(sub, ast.Call):
                            rel = self._match_release(sub)
                            if rel is not None:
                                protected.add(rel[0].name)
            self._protect.append(protected)
            before = set(id(t) for t in self.tokens)
            self._stmts(stmt.body)
            self._stmts(stmt.orelse)
            self._protect.pop()
            # tokens acquired inside the try body are suspended while
            # walking the except handlers: the dominant pattern is
            # ``try: fd = os.open(...) except OSError: return None`` —
            # in that path the acquire itself failed, nothing is held
            acquired_in_body = [t for t in self.tokens
                                if id(t) not in before]
            saved = [(t, t.state) for t in acquired_in_body]
            for t in acquired_in_body:
                if t.state == "held":
                    t.state = "released"
            for handler in stmt.handlers:
                self._stmts(handler.body)
            for t, st in saved:
                if t.state == "released":
                    t.state = st
            self._stmts(stmt.finalbody)
            return

        # default: releases/escapes inside, no control flow
        self._handle_calls(stmt)

    def _stmt_settles(self, stmt: ast.stmt, t: _Token) -> bool:
        """True when ``stmt`` itself releases or escapes ``t`` — then
        it is not an exception hazard *for that token* (if it raises,
        the release raced the failure; treating that as a leak would
        flag every `release()` call that can itself fail)."""
        tnames = t.names()
        for sub in ast.walk(stmt):
            if not isinstance(sub, ast.Call):
                continue
            rel = self._match_release(sub)
            if rel is not None and rel[0].name == t.spec.name \
                    and (rel[2] or t.key_matches(rel[1])):
                return True
            if self._is_borrow(t.spec, sub):
                continue
            for arg in list(sub.args) + [kw.value for kw in sub.keywords]:
                for inner in ast.walk(arg):
                    if isinstance(inner, ast.Name) and inner.id in tnames:
                        return True
        names = {n.id for n in ast.walk(stmt) if isinstance(n, ast.Name)}
        if isinstance(stmt, (ast.Return, ast.Assign)) and tnames & names:
            return True
        return False

    def _branch_if(self, stmt: ast.If) -> None:
        """If handling with result-check refinement: for a ``checked``
        acquire, ``if tok is None: ...`` / ``if not ok: ...`` drops the
        token in the failure branch (nothing was acquired there)."""
        self._handle_calls(stmt.test)
        acq = self._acquire_in(stmt.test)
        if acq is not None:
            spec, call = acq
            if spec.checked:
                key = None
                if spec.key_arg is not None \
                        and len(call.args) > spec.key_arg:
                    key = _expr_src(call.args[spec.key_arg])
                if key is not None:
                    positive_body = not isinstance(stmt.test,
                                                   ast.UnaryOp)
                    tok = _Token(spec, key, stmt.lineno)
                    tok.protected = self._protected(spec)
                    self.tokens.append(tok)
                    if positive_body:
                        # held only inside the body
                        self._stmts(stmt.body)
                        tok.state = "escaped" if tok.state == "held" \
                            else tok.state
                        saved = tok.state
                        self._stmts(stmt.orelse)
                        tok.state = saved
                    else:
                        # `if not acquire(): break/return` — held on
                        # the fallthrough
                        self._stmts(stmt.body)
                        self._stmts(stmt.orelse)
                    return
        failure, success = self._none_guard(stmt.test)
        if failure is not None:
            # the token's value is None/falsy in the body — the acquire
            # failed on that path, so nothing is held while walking it
            for t in self._live():
                if failure in t.names():
                    t.state = "released"
                    self._stmts(stmt.body)
                    if t.state == "released":
                        t.state = "held"
                    self._stmts(stmt.orelse)
                    return
        if success is not None:
            for t in self._live():
                if success in t.names():
                    self._stmts(stmt.body)
                    body_state = t.state
                    t.state = "released"  # not held in the else branch
                    self._stmts(stmt.orelse)
                    if t.state == "released":
                        t.state = body_state
                    return
        self._stmts(stmt.body)
        self._stmts(stmt.orelse)

    @staticmethod
    def _none_guard(test: ast.AST) -> Tuple[Optional[str], Optional[str]]:
        """(failure-name, success-name): ``x is None`` / ``not x`` put
        the token's FAILURE branch in the body; ``x is not None`` / a
        bare name put the SUCCESS branch there."""
        if isinstance(test, ast.Compare) and len(test.ops) == 1 \
                and isinstance(test.comparators[0], ast.Constant) \
                and test.comparators[0].value is None:
            name = _expr_src(test.left)
            if isinstance(test.ops[0], ast.Is):
                return name, None
            if isinstance(test.ops[0], ast.IsNot):
                return None, name
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            inner = test.operand
            if isinstance(inner, (ast.Name, ast.Attribute)):
                return _expr_src(inner), None
        if isinstance(test, (ast.Name, ast.Attribute)):
            return None, _expr_src(test)
        return None, None


# ---------------------------------------------------------------------------
# summarize_module
# ---------------------------------------------------------------------------

def summarize_module(path: str, source: str,
                     tree: Optional[ast.Module] = None,
                     specs: Sequence[ResourceSpec] = RESOURCE_SPECS
                     ) -> ModuleSummary:
    if tree is None:
        tree = ast.parse(source, filename=path)
    aliases = _collect_aliases(tree)
    dotted_mod = module_dotted(path)
    ms = ModuleSummary(
        path=path,
        sha=hashlib.sha256(source.encode()).hexdigest(),
        dotted=dotted_mod, aliases=aliases,
        lock_defs=_collect_lock_defs(tree, aliases))

    module_funcs = {n.name for n in tree.body
                    if isinstance(n, (ast.FunctionDef,
                                      ast.AsyncFunctionDef))}

    def _summarize_fn(node, cls_name: str) -> None:
        qual = f"{cls_name}.{node.name}" if cls_name else node.name
        fs = FuncSummary(
            qual=qual, cls=cls_name, name=node.name, line=node.lineno,
            is_async=isinstance(node, ast.AsyncFunctionDef),
            params=tuple(a.arg for a in node.args.args))
        _FunctionWalker(fs, ms.lock_defs, aliases, cls_name).walk(node.body)
        sf = _StateFactsVisitor(fs)
        for stmt in node.body:
            sf.visit(stmt)
        _detect_retry_forward(node, fs, aliases)
        end = max((getattr(n, "lineno", node.lineno)
                   for n in ast.walk(node)), default=node.lineno)
        _ResourceScanner(fs, aliases, specs).walk(node.body, end)
        ms.functions[qual] = fs
        if node.name.startswith("handle_"):
            ms.handlers.append(node.name[len("handle_"):])

    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            _summarize_fn(node, "")
        elif isinstance(node, ast.ClassDef):
            bases = []
            for b in node.bases:
                d = _resolve_dotted(aliases, _dotted(b))
                if d is not None:
                    bases.append(d if "." in d else f"{dotted_mod}.{d}")
            ms.classes[node.name] = {
                "bases": bases,
                "attrs": _collect_attr_binds(node, aliases,
                                             module_funcs, dotted_mod),
            }
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    _summarize_fn(sub, node.name)

    # derived-signal definitions (metric-drift consults the whole tree)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            if d is not None and d.split(".")[-1] == "RecordingRule":
                for kw in node.keywords:
                    if kw.arg == "name" \
                            and isinstance(kw.value, ast.Constant) \
                            and isinstance(kw.value.value, str):
                        ms.signals.append(kw.value.value)
    return ms


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def default_cache_path(root: str) -> str:
    return os.path.join(root, "build", "rtpu-check-summaries.pkl")


class SummaryCache:
    """Content-hash-keyed persistence of module summaries.  The cache
    file lives under ``build/`` (gitignored, wiped by ``make clean``);
    a version or spec-fingerprint mismatch drops it wholesale.  Pickle,
    not JSON: the doc holds every per-function summary in the tree
    (~hundreds of thousands of nodes) and is rewritten whole on any
    edit, so codec speed is what keeps ``--changed-only`` sub-second —
    same local-build-artifact trust model as ``.pyc``."""

    def __init__(self, path: Optional[str],
                 specs: Sequence[ResourceSpec] = RESOURCE_SPECS):
        self.path = path
        self._fp = _spec_fingerprint(specs)
        self._entries: Dict[str, Dict[str, Any]] = {}
        self._dirty = False
        self.hits = 0
        self.misses = 0
        if path is not None and os.path.exists(path):
            try:
                with open(path, "rb") as f:
                    data = pickle.load(f)
                if data.get("version") == CACHE_VERSION \
                        and data.get("specs") == self._fp:
                    self._entries = data.get("modules", {})
            except (OSError, ValueError, EOFError, AttributeError,
                    ImportError, pickle.PickleError):
                self._entries = {}

    def get(self, path: str, sha: str) -> Optional[ModuleSummary]:
        ent = self._entries.get(path)
        if ent is not None and ent.get("sha") == sha:
            self.hits += 1
            try:
                return ModuleSummary.from_dict(ent["summary"])
            except (KeyError, TypeError):  # pragma: no cover - corrupt
                pass
        self.misses += 1
        return None

    def put(self, summary: ModuleSummary) -> None:
        self._entries[summary.path] = {
            "sha": summary.sha, "summary": summary.to_dict()}
        self._dirty = True

    def save(self) -> None:
        # a fully-warm run re-summarized nothing: skip the (large)
        # re-serialization entirely
        if self.path is None or not self._dirty:
            return
        try:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            tmp = f"{self.path}.{os.getpid()}.tmp"  # no shared tmp
            with open(tmp, "wb") as f:
                pickle.dump({"version": CACHE_VERSION, "specs": self._fp,
                             "modules": self._entries}, f,
                            protocol=pickle.HIGHEST_PROTOCOL)
            os.replace(tmp, self.path)
        except OSError:  # pragma: no cover - cache is best-effort
            pass


# ---------------------------------------------------------------------------
# project index
# ---------------------------------------------------------------------------

class ProjectIndex:
    """The resolved whole-program view: module summaries keyed by path,
    a global function table, class registry, call resolution, and the
    transitive fixed points the rules consume."""

    def __init__(self) -> None:
        self.modules: Dict[str, ModuleSummary] = {}
        self.by_dotted: Dict[str, str] = {}
        self.classes: Dict[str, Tuple[str, str]] = {}
        self.functions: Dict[str, FuncSummary] = {}   # fid -> summary
        self._fn_module: Dict[str, str] = {}          # fid -> path
        self._resolve_memo: Dict[Tuple, Optional[str]] = {}
        self._trans_locks: Optional[Dict[str, Set[str]]] = None
        self._trans_rpc: Optional[Dict[str, Set[str]]] = None
        self._callees_memo: Dict[str, List[Tuple[str, int]]] = {}

    # -- construction -----------------------------------------------------
    @classmethod
    def build(cls, summaries: Iterable[ModuleSummary]) -> "ProjectIndex":
        idx = cls()
        for ms in summaries:
            idx.add(ms)
        return idx

    def add(self, ms: ModuleSummary) -> None:
        self.modules[ms.path] = ms
        self.by_dotted[ms.dotted] = ms.path
        for cname in ms.classes:
            self.classes[f"{ms.dotted}.{cname}"] = (ms.path, cname)
        for qual, fs in ms.functions.items():
            fid = f"{ms.path}::{qual}"
            self.functions[fid] = fs
            self._fn_module[fid] = ms.path

    @classmethod
    def from_tree(cls, root: str,
                  cache: Optional[SummaryCache] = None,
                  extra_sources: Optional[Dict[str, str]] = None,
                  specs: Sequence[ResourceSpec] = RESOURCE_SPECS
                  ) -> "ProjectIndex":
        """Index every ``ray_tpu/`` module under ``root``, consulting
        ``cache`` by content hash.  ``extra_sources`` (path -> source)
        overrides/augments the on-disk tree (used by tests and by
        scans whose contexts were already read)."""
        summaries: List[ModuleSummary] = []
        sources: Dict[str, str] = dict(extra_sources or {})
        pkg = os.path.join(root, "ray_tpu")
        if os.path.isdir(pkg):
            for dirpath, dirnames, filenames in os.walk(pkg):
                dirnames[:] = sorted(d for d in dirnames
                                     if d != "__pycache__"
                                     and not d.startswith("."))
                for fn in sorted(filenames):
                    if not fn.endswith(".py"):
                        continue
                    rel = os.path.relpath(
                        os.path.join(dirpath, fn), root
                    ).replace(os.sep, "/")
                    if rel in sources:
                        continue
                    try:
                        with open(os.path.join(dirpath, fn),
                                  encoding="utf-8") as f:
                            sources[rel] = f.read()
                    except OSError:
                        continue
        for rel in sorted(sources):
            source = sources[rel]
            sha = hashlib.sha256(source.encode()).hexdigest()
            ms = cache.get(rel, sha) if cache is not None else None
            if ms is None:
                try:
                    ms = summarize_module(rel, source, specs=specs)
                except SyntaxError:
                    continue
                if cache is not None:
                    cache.put(ms)
            summaries.append(ms)
        return cls.build(summaries)

    # -- registries -------------------------------------------------------
    def all_handlers(self) -> Dict[str, List[Tuple[str, str, int]]]:
        """method -> [(path, qual, line)] over the whole tree."""
        out: Dict[str, List[Tuple[str, str, int]]] = {}
        for path, ms in self.modules.items():
            for qual, fs in ms.functions.items():
                if fs.name.startswith("handle_"):
                    out.setdefault(fs.name[len("handle_"):], []).append(
                        (path, qual, fs.line))
        return out

    def all_signals(self) -> Set[str]:
        return {s for ms in self.modules.values() for s in ms.signals}

    def dependents(self, paths: Iterable[str]) -> Set[str]:
        """Modules that import (directly) any of ``paths`` — the
        ``--changed-only`` blast radius."""
        targets = {self.modules[p].dotted for p in paths
                   if p in self.modules}
        out: Set[str] = set()
        for path, ms in self.modules.items():
            for dotted in ms.aliases.values():
                d = dotted
                while d:
                    if d in targets:
                        out.add(path)
                        break
                    d = d.rpartition(".")[0]
                else:
                    continue
                break
        return out

    # -- call resolution --------------------------------------------------
    def _class_function(self, dotted_cls: str, meth: str,
                        depth: int = 0) -> Optional[str]:
        ent = self.classes.get(dotted_cls)
        if ent is None or depth > 6:
            return None
        path, cname = ent
        ms = self.modules[path]
        qual = f"{cname}.{meth}"
        if qual in ms.functions:
            return f"{path}::{qual}"
        for base in ms.classes[cname]["bases"]:
            hit = self._class_function(base, meth, depth + 1)
            if hit is not None:
                return hit
        return None

    def _module_function(self, dotted: str) -> Optional[str]:
        """``pkg.mod.func`` (or ``pkg.mod.Class.meth``) -> fid."""
        mod, _, name = dotted.rpartition(".")
        if not mod:
            return None
        path = self.by_dotted.get(mod)
        if path is not None:
            ms = self.modules[path]
            if name in ms.functions:
                return f"{path}::{name}"
            if name in ms.classes:  # constructor: Class() -> __init__
                return self._class_function(dotted, "__init__")
        # Class.meth spelled dotted (mod.Class.meth)
        mod2, _, cls_name = mod.rpartition(".")
        if mod2 and self.by_dotted.get(mod2) is not None \
                and cls_name[:1].isupper():
            return self._class_function(f"{mod2}.{cls_name}", name)
        return None

    def resolve_call(self, path: str, fs: FuncSummary,
                     kind: str, a: str, b: str) -> Optional[str]:
        memo_key = (path, fs.cls, kind, a, b)
        if memo_key in self._resolve_memo:
            return self._resolve_memo[memo_key]
        out = self._resolve_call(path, fs, kind, a, b)
        self._resolve_memo[memo_key] = out
        return out

    def _resolve_call(self, path: str, fs: FuncSummary,
                      kind: str, a: str, b: str) -> Optional[str]:
        ms = self.modules.get(path)
        if ms is None:
            return None
        if kind == "self":
            if fs.cls:
                hit = self._class_function(f"{ms.dotted}.{fs.cls}", a)
                if hit is not None:
                    return hit
            # self.<attr>() where attr is a bound callable
            if fs.cls and fs.cls in ms.classes:
                bind = ms.classes[fs.cls]["attrs"].get(a)
                if bind is not None and bind.startswith("F:"):
                    return self._module_function(bind[2:])
            return None
        if kind == "attr":
            if fs.cls and fs.cls in ms.classes:
                bind = ms.classes[fs.cls]["attrs"].get(a)
                if bind is not None:
                    if bind.startswith("C:"):
                        return self._class_function(bind[2:], b)
                    if bind.startswith("F:") and not b:
                        return self._module_function(bind[2:])
            return None
        if kind == "local":
            # <name>.<meth> — try the name as a module alias first
            d = ms.aliases.get(a)
            if d is not None:
                return self._module_function(f"{d}.{b}")
            return None
        if kind == "dotted":
            d = a
            head, _, rest = d.partition(".")
            canon = ms.aliases.get(head)
            if canon is not None:
                d = f"{canon}.{rest}" if rest else canon
            elif "." not in d:
                if d in ms.functions:
                    return f"{path}::{d}"
                if d in ms.classes:
                    return self._class_function(f"{ms.dotted}.{d}",
                                                "__init__")
                return None
            return self._module_function(d)
        return None

    def callees(self, fid: str) -> List[Tuple[str, int]]:
        """Resolved (callee fid, call line) list of one function."""
        cached = self._callees_memo.get(fid)
        if cached is not None:
            return cached
        fs = self.functions[fid]
        path = self._fn_module[fid]
        out: List[Tuple[str, int]] = []
        for kind, a, b, line, _held in fs.calls:
            tgt = self.resolve_call(path, fs, kind, a, b)
            if tgt is not None and tgt != fid:
                out.append((tgt, line))
        self._callees_memo[fid] = out
        return out

    # -- transitive fixed points ------------------------------------------
    def lock_id(self, path: str, lockref: str) -> str:
        scope, _, sym = lockref.partition("::")
        return f"{path}::{scope}.{sym}" if scope else f"{path}::{sym}"

    def lock_kind(self, lock_id: str) -> str:
        path, _, rest = lock_id.partition("::")
        scope, _, sym = rest.rpartition(".")
        ms = self.modules.get(path)
        if ms is None:
            return "lock"
        d = ms.lock_defs.get(f"{scope}::{sym}")
        return d["kind"] if d else "lock"

    def _fixed_point(self, direct: Dict[str, Set[str]]
                     ) -> Dict[str, Set[str]]:
        out = {fid: set(v) for fid, v in direct.items()}
        edges: Dict[str, List[str]] = {
            fid: [c for c, _ in self.callees(fid)]
            for fid in self.functions}
        changed = True
        while changed:
            changed = False
            for fid, callees in edges.items():
                cur = out.setdefault(fid, set())
                before = len(cur)
                for c in callees:
                    cur |= out.get(c, set())
                if len(cur) != before:
                    changed = True
        return out

    def transitive_locks(self) -> Dict[str, Set[str]]:
        """fid -> every lock id it may acquire, directly or through
        resolved callees."""
        if self._trans_locks is None:
            direct = {
                fid: {self.lock_id(self._fn_module[fid], ref)
                      for ref, _ln, _held in fs.acquires}
                for fid, fs in self.functions.items()}
            self._trans_locks = self._fixed_point(direct)
        return self._trans_locks

    def transitive_rpcs(self) -> Dict[str, Set[str]]:
        """fid -> blocking RPC markers reachable from it.  Only SYNC
        reachability counts: an async callee's awaited RPC parks the
        caller's coroutine (the per-file await-under-lock rule owns
        that); what this tracks is a *thread* blocking inside a sync
        call chain."""
        if self._trans_rpc is None:
            direct: Dict[str, Set[str]] = {}
            for fid, fs in self.functions.items():
                marks = {f"{m}" for m, kind, _ln, _held, _idem in fs.rpcs
                         if kind == "client"}
                direct[fid] = marks
            # restrict propagation to sync callees: an awaited coroutine
            # does not block the thread that owns the lock
            out = {fid: set(v) for fid, v in direct.items()}
            edges = {
                fid: [c for c, _ in self.callees(fid)
                      if not self.functions[c].is_async]
                for fid in self.functions}
            changed = True
            while changed:
                changed = False
                for fid, callees in edges.items():
                    cur = out.setdefault(fid, set())
                    before = len(cur)
                    for c in callees:
                        cur |= out.get(c, set())
                    if len(cur) != before:
                        changed = True
            self._trans_rpc = out
        return self._trans_rpc

    # -- witness chains ---------------------------------------------------
    def find_chain(self, start: str,
                   want: Callable[[str], Optional[int]],
                   sync_only: bool = False
                   ) -> Optional[List[Tuple[str, int]]]:
        """BFS from ``start`` to the nearest function where ``want``
        returns a line number; the chain is [(fid, line-at-which-the-
        next-hop-happens), ..., (final fid, target line)]."""
        hit = want(start)
        if hit is not None:
            return [(start, hit)]
        parents: Dict[str, Tuple[str, int]] = {}
        queue = [start]
        seen = {start}
        while queue:
            cur = queue.pop(0)
            for callee, line in self.callees(cur):
                if callee in seen:
                    continue
                if sync_only and self.functions[callee].is_async:
                    continue
                seen.add(callee)
                parents[callee] = (cur, line)
                hit = want(callee)
                if hit is not None:
                    chain: List[Tuple[str, int]] = [(callee, hit)]
                    node = callee
                    while node in parents:
                        parent, pline = parents[node]
                        chain.insert(0, (parent, pline))
                        node = parent
                    return chain
                queue.append(callee)
        return None

    def render_fid(self, fid: str) -> str:
        path, _, qual = fid.partition("::")
        return f"{path}:{qual}"

    def render_chain(self, chain: List[Tuple[str, int]]) -> str:
        return " -> ".join(f"{self.render_fid(fid)}:{line}"
                           for fid, line in chain)


def index_for(contexts: Sequence[Any], cfg: Any,
              cache: Optional[SummaryCache] = None) -> ProjectIndex:
    """The project index for one run: scanned contexts (any objects
    with ``.path``/``.source``) override the on-disk tree under
    ``cfg.root``.  Memoized on the config object so the three
    interprocedural rules — and the registry consumers in project.py —
    build it exactly once per run (and per test fixture)."""
    idx = getattr(cfg, "ipa_index", None)
    if idx is not None:
        return idx
    idx = ProjectIndex.from_tree(
        cfg.root, cache=cache,
        extra_sources={c.path: c.source for c in contexts})
    cfg.ipa_index = idx
    return idx
