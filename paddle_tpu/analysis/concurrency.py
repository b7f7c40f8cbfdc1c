"""Concurrency lint for the serving/gateway/core stack.

The last several PRs each burned review rounds on the same thread-safety
bug shapes (duplicate live-bucket entries, double-reroute, respawn racing
scale_to, enqueue-after-sweep). This analyzer models each class's
``with self._lock:`` scopes statically and reports the three shapes:

* ``unguarded-mutation`` — an instance attribute (or module global) that is
  mutated inside a lock scope somewhere but also mutated — or mutated while
  being read under the lock elsewhere — outside any lock scope. The
  outside-the-lock site is the finding. Mutations in ``__init__`` /
  ``__post_init__`` are construction (happens-before publication) and never
  count. The **GIL-atomic bump pattern** — a single-statement module-level
  dict write inside a function whose docstring says ``GIL`` (e.g.
  ``serving.metrics.bump``) — is a documented allowed pattern, not a
  finding (docs/static_analysis.md).
* ``lock-order-cycle`` — class A acquires B's lock (directly, or by calling
  a B method that takes its own lock) while holding its own, and B does the
  reverse: the classic ABBA deadlock, detected as a cycle in the
  lock-acquisition graph across all analyzed files.
* ``blocking-call-in-lock`` — ``time.sleep``, ``Thread.join``, socket/HTTP
  IO, or a serving engine step/prefill/drain call made while holding a
  lock: every other thread contending on that lock stalls behind device
  latency. Where the lock IS the intended serialization point (the
  ``ServingAPI`` pump), the site carries an inline allow() saying so.

Scope: ``paddle_tpu/serving/`` (gateway included) and ``paddle_tpu/core/``
by default — the threaded subsystems. Pure AST; nested ``def``s are
analyzed as their own functions (a closure does not inherit the lock depth
of the ``with`` block it is defined in — it runs later, on another thread).
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from .common import Finding, SourceFile

#: attribute calls that mutate their receiver in place
_MUTATOR_METHODS = {
    "append", "extend", "insert", "remove", "pop", "popitem", "clear",
    "update", "add", "discard", "setdefault", "sort", "reverse",
    "appendleft", "popleft", "extendleft",
}

#: serving calls that block on device/compile latency — holding a lock
#: across one stalls every contending thread behind the accelerator
_BLOCKING_SERVING_CALLS = {
    "decode_step", "decode_turn", "decode_collect", "prefill", "admit",
    "step", "_step_guarded", "_pump_once", "run_until_idle", "drain",
}

_SOCKET_CALLS = {"urlopen", "recv", "accept", "getaddrinfo",
                 "create_connection"}

_CTOR_EXEMPT = {"__init__", "__post_init__", "__new__", "__del__"}


def _is_lock_ctor(node: ast.AST) -> bool:
    """``threading.Lock()`` / ``threading.RLock()`` / bare ``Lock()``."""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    name = f.attr if isinstance(f, ast.Attribute) else (
        f.id if isinstance(f, ast.Name) else "")
    return name in ("Lock", "RLock")


def _self_attr(node: ast.AST) -> Optional[str]:
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


@dataclass
class _ClassInfo:
    name: str
    file: SourceFile
    node: ast.ClassDef
    lock_attrs: Set[str] = field(default_factory=set)
    #: attr -> class name it was constructed from in __init__
    attr_types: Dict[str, str] = field(default_factory=dict)
    #: attrs assigned threading.Thread(...) (for the .join() heuristic)
    thread_attrs: Set[str] = field(default_factory=set)
    #: methods that acquire self's own lock somewhere in their body
    locking_methods: Set[str] = field(default_factory=set)


@dataclass
class _MutationRecord:
    in_lock: List[Tuple[int, str]] = field(default_factory=list)
    out_lock: List[Tuple[int, str]] = field(default_factory=list)
    read_in_lock: bool = False


class _FunctionScan(ast.NodeVisitor):
    """Walk ONE function body tracking lock depth. Does not descend into
    nested function/class definitions (they are scanned separately with a
    fresh depth — a closure runs outside the with-block that defines it)."""

    def __init__(self, analyzer: "ConcurrencyAnalyzer", sf: SourceFile,
                 cls: Optional[_ClassInfo], fn_name: str,
                 module_locks: Set[str], module_mutables: Set[str]):
        self.an = analyzer
        self.sf = sf
        self.cls = cls
        self.fn_name = fn_name
        self.module_locks = module_locks
        self.module_mutables = module_mutables
        self.own_depth = 0      # holding this class's (or module's) lock
        self.held: List[str] = []  # lock identities, outermost first
        self.gil_pattern_ok = False  # function documents the GIL idiom

    # ------------------------------------------------------------ helpers

    def _lock_identity(self, expr: ast.AST) -> Optional[str]:
        """Identity of an acquired lock expression, or None if not a lock.

        ``self._lock`` -> "Class:C"; module ``_lock`` -> "module:<rel>";
        ``other._lock`` where ``other``'s class is inferable -> "Class:D".
        """
        attr = _self_attr(expr)
        if attr is not None and self.cls is not None:
            if attr in self.cls.lock_attrs:
                return f"Class:{self.cls.name}"
            # self.<obj>._lock style is an Attribute of an Attribute
            return None
        if isinstance(expr, ast.Name):
            if expr.id in self.module_locks:
                return f"module:{self.sf.relpath}"
            return None
        if isinstance(expr, ast.Attribute) and expr.attr.endswith("lock"):
            base = expr.value
            base_attr = _self_attr(base)
            if base_attr is not None and self.cls is not None:
                tname = self.cls.attr_types.get(base_attr)
                if tname and tname in self.an.classes:
                    return f"Class:{tname}"
            # locals/params are untyped here: fall back to an attr-name
            # identity so nested acquisition still registers an edge
            return f"?:{expr.attr}"
        return None

    def _record_mut(self, key: str, line: int) -> None:
        rec = self.an.mutations.setdefault(key, _MutationRecord())
        (rec.in_lock if self.own_depth > 0 else rec.out_lock).append(
            (line, f"{self.sf.relpath}:{self.fn_name}"))

    def _key_for_self_attr(self, attr: str) -> Optional[str]:
        if self.cls is None or not self.cls.lock_attrs:
            return None  # no lock in this class: nothing to guard against
        if attr in self.cls.lock_attrs:
            return None
        if self.fn_name.rsplit(".", 1)[-1] in _CTOR_EXEMPT:
            return None
        return f"{self.sf.relpath}::{self.cls.name}.{attr}"

    def _key_for_global(self, name: str) -> Optional[str]:
        if name not in self.module_mutables:
            return None
        if f"module:{self.sf.relpath}" not in self.an.module_lock_files:
            return None  # module has no lock: nothing to guard against
        if self.fn_name == "<module>":
            return None  # import-time init happens before threads exist
        return f"{self.sf.relpath}::{name}"

    # ------------------------------------------------------------- visits

    def visit_With(self, node: ast.With) -> None:
        acquired: List[str] = []
        for item in node.items:
            ident = self._lock_identity(item.context_expr)
            if ident is not None:
                acquired.append(ident)
        own = (f"Class:{self.cls.name}" if self.cls is not None
               else f"module:{self.sf.relpath}")
        own_acquired = sum(1 for a in acquired if a == own)
        for a in acquired:
            if self.held and self.held[-1] != a:
                self.an.lock_edges.setdefault(
                    (self.held[-1], a), (self.sf, node.lineno,
                                         self.fn_name))
            self.held.append(a)
        self.own_depth += own_acquired
        if self.cls is not None and own_acquired:
            self.cls.locking_methods.add(self.fn_name.rsplit(".", 1)[-1])
        for stmt in node.body:
            self.visit(stmt)
        self.own_depth -= own_acquired
        del self.held[len(self.held) - len(acquired):len(self.held)]

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        pass  # nested defs scanned separately with a fresh lock depth

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        pass

    def visit_Lambda(self, node: ast.Lambda) -> None:
        pass

    def _mutation_target(self, target: ast.AST, line: int) -> None:
        attr = _self_attr(target)
        if attr is not None:
            key = self._key_for_self_attr(attr)
            if key:
                self._record_mut(key, line)
            return
        if isinstance(target, ast.Name):
            key = self._key_for_global(target.id)
            if key:
                self._record_mut(key, line)
            return
        if isinstance(target, ast.Subscript):
            base = target.value
            battr = _self_attr(base)
            if battr is not None:
                key = self._key_for_self_attr(battr)
                if key:
                    self._record_mut(key, line)
            elif isinstance(base, ast.Name):
                key = self._key_for_global(base.id)
                if key:
                    if self.own_depth == 0 and self.gil_pattern_ok:
                        return  # documented GIL-atomic single-key bump
                    self._record_mut(key, line)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._mutation_target(elt, line)

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            self._mutation_target(t, node.lineno)
        self.visit(node.value)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._mutation_target(node.target, node.lineno)
        self.visit(node.value)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._mutation_target(node.target, node.lineno)
            self.visit(node.value)

    def visit_Delete(self, node: ast.Delete) -> None:
        for t in node.targets:
            self._mutation_target(t, node.lineno)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        # reads of guarded state while holding the lock
        if isinstance(node.ctx, ast.Load) and self.own_depth > 0:
            attr = _self_attr(node)
            if attr is not None:
                key = self._key_for_self_attr(attr)
                if key:
                    self.an.mutations.setdefault(
                        key, _MutationRecord()).read_in_lock = True
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load) and self.own_depth > 0:
            key = self._key_for_global(node.id)
            if key:
                self.an.mutations.setdefault(
                    key, _MutationRecord()).read_in_lock = True

    def visit_Call(self, node: ast.Call) -> None:
        f = node.func
        # in-place mutator methods on guarded state
        if isinstance(f, ast.Attribute) and f.attr in _MUTATOR_METHODS:
            recv = f.value
            battr = _self_attr(recv)
            if battr is not None:
                key = self._key_for_self_attr(battr)
                if key:
                    self._record_mut(key, node.lineno)
            elif isinstance(recv, ast.Name):
                key = self._key_for_global(recv.id)
                if key:
                    self._record_mut(key, node.lineno)
        if self.held:
            self._check_blocking(node)
        self._check_cross_class_call(node)
        self.generic_visit(node)

    # ------------------------------------------------- blocking under lock

    def _check_blocking(self, node: ast.Call) -> None:
        f = node.func
        what = None
        if isinstance(f, ast.Attribute):
            recv = f.value
            if (f.attr == "sleep" and isinstance(recv, ast.Name)
                    and recv.id == "time"):
                what = "time.sleep()"
            elif f.attr == "join" and not isinstance(recv, ast.Constant):
                names = ast.dump(recv)
                thready = any(s in names.lower()
                              for s in ("thread", "proc", "worker"))
                battr = _self_attr(recv)
                if battr is not None and self.cls is not None:
                    thready = thready or battr in self.cls.thread_attrs
                if thready:
                    what = f"{ast.unparse(recv)}.join()"
            elif f.attr in _SOCKET_CALLS:
                what = f"socket/HTTP call .{f.attr}()"
            elif (isinstance(recv, ast.Name) and recv.id == "socket"):
                what = f"socket.{f.attr}()"
            elif f.attr in _BLOCKING_SERVING_CALLS:
                what = f"engine/scheduler call .{f.attr}()"
        elif isinstance(f, ast.Name):
            if f.id == "sleep":
                what = "sleep()"
            elif f.id == "urlopen":
                what = "urlopen()"
            elif f.id in _BLOCKING_SERVING_CALLS:
                what = f"{f.id}()"
        if what is not None:
            self.an.findings.append(self.sf.finding(
                "blocking-call-in-lock", node.lineno,
                f"{what} while holding {self.held[-1].split(':')[-1]}'s "
                f"lock: every thread contending on the lock stalls behind "
                f"this call"))

    # --------------------------------------------------- lock-order edges

    def _check_cross_class_call(self, node: ast.Call) -> None:
        """Holding our own lock, a call into another class's
        lock-acquiring method is a lock-acquisition edge."""
        if self.own_depth == 0 or self.cls is None:
            return
        f = node.func
        if not isinstance(f, ast.Attribute):
            return
        recv = f.value
        battr = _self_attr(recv)
        if battr is None:
            return
        tname = self.cls.attr_types.get(battr)
        target = self.an.classes.get(tname or "")
        if target is None or not target.lock_attrs:
            return
        if f.attr in target.locking_methods:
            self.an.lock_edges.setdefault(
                (f"Class:{self.cls.name}", f"Class:{target.name}"),
                (self.sf, node.lineno, self.fn_name))


class ConcurrencyAnalyzer:
    name = "concurrency"
    rules = ("unguarded-mutation", "lock-order-cycle",
             "blocking-call-in-lock")

    def relevant(self, relpath: str) -> bool:
        return (relpath.startswith("paddle_tpu/serving")
                or relpath.startswith("paddle_tpu/core"))

    def analyze(self, corpus: List[SourceFile]) -> List[Finding]:
        files = [sf for sf in corpus
                 if sf.tree is not None and self.relevant(sf.relpath)]
        self.classes: Dict[str, _ClassInfo] = {}
        self.mutations: Dict[str, _MutationRecord] = {}
        self.lock_edges: Dict[Tuple[str, str],
                              Tuple[SourceFile, int, str]] = {}
        self.module_lock_files: Set[str] = set()
        self.findings: List[Finding] = []
        per_file: Dict[str, Tuple[Set[str], Set[str]]] = {}

        # pass 1: classes, lock attrs, attr types, module locks/mutables
        for sf in files:
            module_locks: Set[str] = set()
            module_mutables: Set[str] = set()
            for node in sf.tree.body:
                if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name):
                    name = node.targets[0].id
                    if _is_lock_ctor(node.value):
                        module_locks.add(name)
                    elif isinstance(node.value, (ast.Dict, ast.List,
                                                 ast.Set, ast.DictComp,
                                                 ast.ListComp, ast.SetComp)):
                        module_mutables.add(name)
                elif isinstance(node, ast.AnnAssign) and isinstance(
                        node.target, ast.Name) and isinstance(
                        node.value, (ast.Dict, ast.List, ast.Set)):
                    module_mutables.add(node.target.id)
            if module_locks:
                self.module_lock_files.add(f"module:{sf.relpath}")
            per_file[sf.relpath] = (module_locks, module_mutables)
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.ClassDef):
                    self._index_class(sf, node)

        self._by_path_cache = {sf.relpath: sf for sf in files}

        # pass 2: scan every function with lock-depth tracking
        for sf in files:
            module_locks, module_mutables = per_file[sf.relpath]
            self._scan_functions(sf, sf.tree, None, "",
                                 module_locks, module_mutables)

        self._report_mutations()
        self._report_cycles()
        return self.findings

    # -------------------------------------------------------------- pass 1

    def _index_class(self, sf: SourceFile, node: ast.ClassDef) -> None:
        info = _ClassInfo(node.name, sf, node)
        # parameter annotations type the attrs they are stored into:
        # ``def __init__(self, router: "Router"): self.router = router``
        param_types: Dict[str, str] = {}
        for sub in node.body:
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for a in (sub.args.posonlyargs + sub.args.args
                          + sub.args.kwonlyargs):
                    ann = a.annotation
                    if isinstance(ann, ast.Name):
                        param_types[a.arg] = ann.id
                    elif isinstance(ann, ast.Constant) and isinstance(
                            ann.value, str):
                        param_types[a.arg] = ann.value.strip('"')
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign):
                for t in sub.targets:
                    attr = _self_attr(t)
                    if attr is not None and isinstance(sub.value, ast.Name) \
                            and sub.value.id in param_types:
                        info.attr_types.setdefault(
                            attr, param_types[sub.value.id])
            if isinstance(sub, ast.Assign):
                for t in sub.targets:
                    attr = _self_attr(t)
                    if attr is None:
                        continue
                    if _is_lock_ctor(sub.value):
                        info.lock_attrs.add(attr)
                    elif isinstance(sub.value, ast.Call):
                        fn = sub.value.func
                        cname = (fn.attr if isinstance(fn, ast.Attribute)
                                 else fn.id if isinstance(fn, ast.Name)
                                 else "")
                        if cname == "Thread":
                            info.thread_attrs.add(attr)
                        elif cname and cname[0].isupper():
                            info.attr_types.setdefault(attr, cname)
                    else:
                        # conditional construction: ``x if c else Cls()``
                        for c in ast.walk(sub.value):
                            if isinstance(c, ast.Call) and isinstance(
                                    c.func, ast.Name) \
                                    and c.func.id[0:1].isupper():
                                info.attr_types.setdefault(attr, c.func.id)
                                break
        # precompute which methods acquire the class's own lock (pass 2
        # consumes this for cross-class edges, so it cannot be lazy — the
        # caller side may be scanned before the callee side)
        if info.lock_attrs:
            for sub in node.body:
                if not isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    continue
                for w in ast.walk(sub):
                    if isinstance(w, ast.With) and any(
                            _self_attr(item.context_expr)
                            in info.lock_attrs for item in w.items):
                        info.locking_methods.add(sub.name)
                        break
        # first definition wins on cross-file name collisions
        self.classes.setdefault(node.name, info)

    # -------------------------------------------------------------- pass 2

    def _scan_functions(self, sf: SourceFile, node: ast.AST,
                        cls: Optional[_ClassInfo], prefix: str,
                        module_locks: Set[str],
                        module_mutables: Set[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                info = self.classes.get(child.name)
                use = info if info is not None and info.node is child else cls
                self._scan_functions(sf, child, use, child.name,
                                     module_locks, module_mutables)
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}.{child.name}" if prefix else child.name
                scan = _FunctionScan(self, sf, cls, qual,
                                     module_locks, module_mutables)
                doc = ast.get_docstring(child) or ""
                scan.gil_pattern_ok = "GIL" in doc
                for stmt in child.body:
                    scan.visit(stmt)
                # nested defs get their own scan (fresh lock depth)
                self._scan_functions(sf, child, cls, qual,
                                     module_locks, module_mutables)

    # ------------------------------------------------------------- reports

    def _report_mutations(self) -> None:
        for key, rec in sorted(self.mutations.items()):
            if not rec.out_lock:
                continue
            if not rec.in_lock and not rec.read_in_lock:
                continue  # never touched under the lock: not lock-protected
            relpath, symbol = key.split("::", 1)
            # findings anchor at every outside-the-lock mutation site
            why = ("also mutated under the lock at "
                   + ", ".join(f"line {ln}" for ln, _ in rec.in_lock[:3])
                   if rec.in_lock else "read under the lock elsewhere")
            for line, fn in rec.out_lock:
                f = self._file_finding(relpath, "unguarded-mutation", line,
                                       f"`{symbol}` mutated outside its "
                                       f"lock scope ({why}): racy "
                                       f"read-modify-write or torn state")
                if f is not None:
                    self.findings.append(f)

    def _file_finding(self, relpath: str, rule: str, line: int,
                      message: str) -> Optional[Finding]:
        sf = self._by_path.get(relpath)
        if sf is None:
            return None
        return sf.finding(rule, line, message)

    @property
    def _by_path(self) -> Dict[str, SourceFile]:
        return self._by_path_cache

    def _report_cycles(self) -> None:
        graph: Dict[str, Set[str]] = {}
        for (a, b) in self.lock_edges:
            graph.setdefault(a, set()).add(b)
        seen_cycles: Set[frozenset] = set()
        for start in sorted(graph):
            stack = [(start, [start])]
            while stack:
                cur, path = stack.pop()
                for nxt in sorted(graph.get(cur, ())):
                    if nxt == start and len(path) > 1:
                        cyc = frozenset(path)
                        if cyc in seen_cycles:
                            continue
                        seen_cycles.add(cyc)
                        sf, line, fn = self.lock_edges[(path[-1], start)]
                        order = " -> ".join(
                            p.split(":")[-1] for p in path + [start])
                        self.findings.append(sf.finding(
                            "lock-order-cycle", line,
                            f"lock acquisition cycle {order}: two threads "
                            f"taking these locks in opposite order "
                            f"deadlock"))
                    elif nxt not in path:
                        stack.append((nxt, path + [nxt]))
