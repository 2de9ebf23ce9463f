"""graft_check framework: parsed modules, findings, baseline, runner.

The suite encodes the cross-cutting invariants the first nine PRs enforced
by hand in review (persist-before-side-effect, no blocking waits in async
or under hot-path locks, shm segments always released, cross-process names
from shared constants, RPC client/server pairing, canonical metric names)
as stdlib-`ast` checkers. Each checker sees every module once (one shared
parse per file) and may also emit tree-wide findings in `finish()`.

Suppressions live in a baseline file (`tools/graft_check/baseline.txt`);
entries match findings by (check_id, path, enclosing symbol) — line-drift
safe — and every entry MUST still match a real finding: stale suppressions
surface as `stale-baseline` findings so the file can only shrink honestly.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import os
import pickle
import re
from typing import (Dict, Iterable, List, Optional, Sequence, Set,
                    Tuple)


@dataclasses.dataclass(frozen=True)
class Finding:
    """One invariant violation at `path:line` (path repo-root-relative)."""

    check_id: str
    path: str
    line: int
    symbol: str  # enclosing `Class.method` / `function` / "<module>"
    message: str

    @property
    def location(self) -> str:
        return f"{self.path}:{self.line}"

    @property
    def key(self) -> Tuple[str, str, str]:
        """Baseline-matching identity (line numbers drift; symbols don't)."""
        return (self.check_id, self.path, self.symbol)

    def render(self) -> str:
        return (f"{self.path}:{self.line}: [{self.check_id}] {self.message} "
                f"(in {self.symbol})")


class ParsedModule:
    """One source file, parsed once and shared by every checker."""

    def __init__(self, root: str, path: str):
        self.path = path
        self.relpath = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path, encoding="utf-8") as f:
            self.source = f.read()
        self.tree = ast.parse(self.source, path)
        self._scopes: Optional[List[Tuple[int, int, str]]] = None

    # -- symbol lookup -----------------------------------------------------

    def _build_scopes(self) -> List[Tuple[int, int, str]]:
        scopes: List[Tuple[int, int, str]] = []

        def walk(node: ast.AST, prefix: str) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.ClassDef)):
                    qual = f"{prefix}.{child.name}" if prefix else child.name
                    scopes.append((child.lineno,
                                   child.end_lineno or child.lineno, qual))
                    walk(child, qual)
                else:
                    walk(child, prefix)

        walk(self.tree, "")
        return scopes

    def symbol_at(self, line: int) -> str:
        """Qualname of the innermost class/function enclosing `line`."""
        if self._scopes is None:
            self._scopes = self._build_scopes()
        best = "<module>"
        best_span = None
        for start, end, qual in self._scopes:
            if start <= line <= end:
                span = end - start
                if best_span is None or span <= best_span:
                    best, best_span = qual, span
        return best

    def finding(self, check_id: str, node: ast.AST, message: str) -> Finding:
        line = getattr(node, "lineno", 0)
        return Finding(check_id, self.relpath, line,
                       self.symbol_at(line), message)


class Checker:
    """One invariant. Subclasses set `ids` (every check id they can emit,
    for --list and --checks filtering) and override `check_module`; tree-
    wide invariants extract per-module picklable facts in `collect` (so
    the on-disk cache can replay them without reparsing) and emit from
    `finish(project)`, which sees the whole tree's facts plus the shared
    call graph."""

    ids: Tuple[Tuple[str, str], ...] = ()  # ((check_id, description), ...)
    #: set to a unique string to have `collect` facts gathered (and cached)
    facts_name: Optional[str] = None

    def check_module(self, mod: ParsedModule) -> Iterable[Finding]:
        """Per-module findings. MUST be a pure function of the module
        contents (results are cached by (path, mtime, size))."""
        return ()

    def collect(self, mod: ParsedModule):
        """Per-module picklable facts for cross-module checks (cached)."""
        return None

    def finish(self, project: "Project" = None) -> Iterable[Finding]:
        """Tree-wide findings, computed from `project` facts/call graph."""
        return ()


# ---------------------------------------------------------------- call utils


def call_target(node: ast.Call) -> Tuple[str, str]:
    """(receiver_text, attr_or_name) for a call — ('time', 'sleep') for
    time.sleep(...), ('', 'foo') for foo(...). Receiver text is the
    unparsed value expression ('self._store' for self._store.put)."""
    fn = node.func
    if isinstance(fn, ast.Name):
        return "", fn.id
    if isinstance(fn, ast.Attribute):
        try:
            base = ast.unparse(fn.value)
        except Exception:  # noqa: BLE001 — exotic expr: best effort
            base = ""
        return base, fn.attr
    return "", ""


def kwarg_value(node: ast.Call, name: str):
    """The literal value of keyword `name`, or None."""
    for kw in node.keywords:
        if kw.arg == name and isinstance(kw.value, ast.Constant):
            return kw.value.value
    return None


def str_head(node: ast.AST) -> Optional[str]:
    """The literal text of a string constant, or the leading literal
    segment of an f-string (enough to check name prefixes)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr) and node.values:
        head = node.values[0]
        if isinstance(head, ast.Constant) and isinstance(head.value, str):
            return head.value
        return ""  # f-string starting with an interpolation: unknown head
    return None


# --------------------------------------------------------- blocking primitives

#: (receiver, attr) pairs that always block the calling thread.
BLOCKING_QUALIFIED = {("time", "sleep")}
#: attrs that block regardless of receiver (sync GCS RPC / channel waits).
BLOCKING_ATTRS = {"rpc", "_wait", "wait_drained", "pull_all", "pull_pages",
                  "serve_put", "instance_put"}
#: ray_tpu module-level blocking APIs.
RAY_BLOCKING = {"get", "wait", "kill"}
#: channel data-plane methods: blocking when the receiver looks like a
#: seqlock channel handle.
CHANNEL_ATTRS = {"read", "write", "write_serialized"}


def is_channel_receiver(base: str) -> bool:
    return "chan" in base.lower() or base in ("ch", "c.ch")


def nonblocking_poll(node: ast.Call) -> bool:
    """True when a `timeout=0`/`timeout_s=0` keyword marks the call as a
    non-blocking poll."""
    return kwarg_value(node, "timeout") == 0 or \
        kwarg_value(node, "timeout_s") == 0


def blocking_call_desc(node: ast.Call) -> Optional[str]:
    """A short description if this call is a known blocking primitive
    (`time.sleep`, a sync `.rpc`, a blocking `ray_tpu.get`, a seqlock
    channel wait), else None. `timeout=0` polls are never blocking."""
    base, attr = call_target(node)
    if not attr:
        return None
    what = f"{base}.{attr}" if base else attr
    if (base, attr) in BLOCKING_QUALIFIED:
        return f"{what}()"
    if nonblocking_poll(node):
        return None
    if base.split(".")[-1] == "ray_tpu" and attr in RAY_BLOCKING:
        return f"blocking {what}()"
    if attr in BLOCKING_ATTRS:
        return f"sync GCS/channel wait {what}()"
    if attr in CHANNEL_ATTRS and is_channel_receiver(base):
        return f"seqlock channel {what}()"
    return None


# ---------------------------------------------------- module summaries / graph

# the word `clock` alone (`clock`, `_clock`, `phase_clock`) is no lock:
# `with self._clock.dispatch(...)` times a call; `_synclock`, `funcLock` are
LOCK_NAME_RE = re.compile(r"(?<!\bc)(?<!_c)lock|mutex|\bmu\b", re.IGNORECASE)


@dataclasses.dataclass
class CallSite:
    """One call expression inside a function body (nested defs excluded —
    their calls belong to the nested function's own summary)."""

    line: int
    recv: str            # '' bare call, 'self'/'cls', or dotted receiver text
    name: str            # function / attribute name
    held: Tuple[str, ...]  # module-local lock tokens lexically held here
    awaited: bool        # directly awaited (returned an awaitable)
    poll: bool           # timeout=0 / timeout_s=0 non-blocking poll


@dataclasses.dataclass
class FuncSummary:
    qualname: str        # 'Class.method', 'func', 'Class.method.inner'
    name: str
    cls: str             # nearest enclosing class name, '' at module level
    is_async: bool
    is_generator: bool
    line: int
    params: Tuple[str, ...]
    calls: List[CallSite] = dataclasses.field(default_factory=list)
    #: direct lock acquisitions: (token, line, tokens-held-before)
    acquires: List[Tuple[str, int, Tuple[str, ...]]] = \
        dataclasses.field(default_factory=list)
    #: direct blocking primitives: (description, line)
    blocking: List[Tuple[str, int]] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ModuleSummary:
    """Everything the interprocedural layer needs from one file —
    picklable, so cache hits skip the parse AND the walk."""

    relpath: str
    functions: Dict[str, FuncSummary]
    classes: Dict[str, Tuple[str, ...]]   # class name -> base-name texts
    toplevel: Set[str]                    # module-level function names
    imports: Dict[str, Tuple[str, str]]   # local name -> (module, orig name)
    import_mods: Dict[str, str]           # local alias -> dotted module


def _lock_token(item: ast.withitem) -> Optional[str]:
    expr = item.context_expr
    if isinstance(expr, ast.Call):
        expr = expr.func
        if isinstance(expr, ast.Attribute) and expr.attr in (
                "acquire", "acquire_timeout"):
            expr = expr.value
    try:
        text = ast.unparse(expr)
    except Exception:  # noqa: BLE001 — exotic expr: not a lock we can name
        return None
    return text if LOCK_NAME_RE.search(text) else None


def _is_generator(node) -> bool:
    """Does this function's OWN body yield (nested defs excluded)?"""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.Yield, ast.YieldFrom)):
            return True
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
            stack.extend(ast.iter_child_nodes(n))
    return False


class _Summarizer(ast.NodeVisitor):
    def __init__(self, mod: ParsedModule):
        self.mod = mod
        self.summary = ModuleSummary(mod.relpath, {}, {}, set(), {}, {})
        self.class_stack: List[str] = []
        self.func_stack: List[FuncSummary] = []
        self.qual_stack: List[str] = []
        self.held: List[str] = []          # lexical with-lock stack
        self.awaited: set = set()          # id() of directly-awaited Calls

    # -- imports -----------------------------------------------------------

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        module = ("." * node.level) + (node.module or "")
        for alias in node.names:
            if alias.name != "*":
                self.summary.imports[alias.asname or alias.name] = (
                    module, alias.name)

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            if alias.asname:
                self.summary.import_mods[alias.asname] = alias.name
            else:
                head = alias.name.split(".")[0]
                self.summary.import_mods[head] = head

    # -- scopes ------------------------------------------------------------

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        bases = []
        for b in node.bases:
            try:
                bases.append(ast.unparse(b))
            except Exception:  # noqa: BLE001
                pass
        self.summary.classes[node.name] = tuple(bases)
        self.class_stack.append(node.name)
        self.qual_stack.append(node.name)
        for child in ast.iter_child_nodes(node):
            self.visit(child)
        self.qual_stack.pop()
        self.class_stack.pop()

    def _visit_func(self, node, is_async: bool) -> None:
        qual = ".".join(self.qual_stack + [node.name])
        params = tuple(a.arg for a in (node.args.posonlyargs
                                       + node.args.args))
        fs = FuncSummary(
            qual, node.name,
            self.class_stack[-1] if self.class_stack else "",
            is_async, _is_generator(node), node.lineno, params)
        self.summary.functions[qual] = fs
        if not self.qual_stack:
            self.summary.toplevel.add(node.name)
        # a nested def under `with lock:` runs later, lock-free
        saved_held, self.held = self.held, []
        self.func_stack.append(fs)
        self.qual_stack.append(node.name)
        for child in ast.iter_child_nodes(node):
            self.visit(child)
        self.qual_stack.pop()
        self.func_stack.pop()
        self.held = saved_held

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._visit_func(node, False)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._visit_func(node, True)

    def visit_Lambda(self, node: ast.Lambda) -> None:
        pass  # lambda bodies run later/elsewhere: skip, stay conservative

    # -- locks -------------------------------------------------------------

    def visit_With(self, node: ast.With) -> None:
        # items acquire in order: `with a, b:` takes b while a is already
        # held, so each item's held-set includes its predecessors
        tokens = []
        for item in node.items:
            tok = _lock_token(item)
            if tok is None:
                continue
            if self.func_stack:
                self.func_stack[-1].acquires.append(
                    (tok, node.lineno, tuple(self.held)))
            self.held.append(tok)
            tokens.append(tok)
        self.generic_visit(node)
        if tokens:
            del self.held[len(self.held) - len(tokens):]

    # `async with` acquires an asyncio primitive — a different (single-
    # threaded) discipline; not part of the thread-lock order graph.

    # -- calls -------------------------------------------------------------

    def visit_Await(self, node: ast.Await) -> None:
        if isinstance(node.value, ast.Call):
            self.awaited.add(id(node.value))
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        if self.func_stack:
            fs = self.func_stack[-1]
            base, attr = call_target(node)
            if attr:
                fs.calls.append(CallSite(
                    node.lineno, base, attr, tuple(self.held),
                    id(node) in self.awaited, nonblocking_poll(node)))
            desc = blocking_call_desc(node)
            if desc is not None:
                fs.blocking.append((desc, node.lineno))
        self.generic_visit(node)


def summarize_module(mod: ParsedModule) -> ModuleSummary:
    s = _Summarizer(mod)
    # two passes so Await marking precedes Call collection order issues:
    # mark awaited calls first (cheap), then summarize
    for n in ast.walk(mod.tree):
        if isinstance(n, ast.Await) and isinstance(n.value, ast.Call):
            s.awaited.add(id(n.value))
    s.visit(mod.tree)
    return s.summary


class CallGraph:
    """Project-wide call resolution over module summaries: bare names to
    same-module / imported module-level functions, `self.`/`cls.` calls to
    methods of the enclosing class (following base-class names), and
    `alias.func(...)` through module imports. Unresolvable calls resolve
    to None — the analyses stay conservative, never guess."""

    def __init__(self, summaries: Dict[str, ModuleSummary]):
        self.summaries = summaries
        #: class name -> [(relpath, class)] for base-class resolution
        self._classes: Dict[str, List[str]] = {}
        for rel, s in summaries.items():
            for cname in s.classes:
                self._classes.setdefault(cname, []).append(rel)
        self._modpath_cache: Dict[Tuple[str, str], Optional[str]] = {}
        self._block_memo: Dict[Tuple[str, str], Optional[List[str]]] = {}
        self._lock_memo: Dict[Tuple[str, str],
                              Dict[str, List[str]]] = {}

    # -- lookup helpers ----------------------------------------------------

    def func(self, relpath: str, qualname: str) -> Optional[FuncSummary]:
        s = self.summaries.get(relpath)
        return s.functions.get(qualname) if s else None

    def _resolve_module(self, relpath: str, dotted: str) -> Optional[str]:
        """Map a dotted import ('ray_tpu._private.poll', '.poll') to a
        scanned relpath, or None when it lives outside the tree."""
        key = (relpath, dotted)
        if key in self._modpath_cache:
            return self._modpath_cache[key]
        result = None
        if dotted.startswith("."):
            level = len(dotted) - len(dotted.lstrip("."))
            parts = [p for p in relpath.split("/")[:-1]]
            parts = parts[:len(parts) - (level - 1)] if level > 1 else parts
            tail = dotted.lstrip(".")
            cand = parts + (tail.split(".") if tail else [])
            for suffix in ("/".join(cand) + ".py",
                           "/".join(cand + ["__init__.py"])):
                if suffix in self.summaries:
                    result = suffix
                    break
        else:
            parts = dotted.split(".")
            for i in range(len(parts)):
                rest = parts[i:]
                for suffix in ("/".join(rest) + ".py",
                               "/".join(rest + ["__init__.py"])):
                    if suffix in self.summaries:
                        result = suffix
                        break
                if result:
                    break
        self._modpath_cache[key] = result
        return result

    def _method_on(self, relpath: str, cls: str, name: str,
                   _seen=None) -> Optional[Tuple[str, FuncSummary]]:
        """`cls.name` in `relpath`'s module, following base-class names
        (same module first, then a globally-unique class of that name)."""
        _seen = _seen or set()
        if (relpath, cls) in _seen:
            return None
        _seen.add((relpath, cls))
        s = self.summaries.get(relpath)
        if s is None:
            return None
        fs = s.functions.get(f"{cls}.{name}")
        if fs is not None:
            return relpath, fs
        for base in s.classes.get(cls, ()):
            base = base.split("[")[0].split(".")[-1]
            if base in s.classes:
                hit = self._method_on(relpath, base, name, _seen)
                if hit:
                    return hit
            elif base in self._classes and len(self._classes[base]) == 1:
                hit = self._method_on(self._classes[base][0], base, name,
                                      _seen)
                if hit:
                    return hit
        return None

    def resolve(self, relpath: str, caller: FuncSummary,
                site: CallSite) -> Optional[Tuple[str, FuncSummary]]:
        """(relpath, FuncSummary) of the project function `site` calls, or
        None (external / dynamic / unresolvable)."""
        s = self.summaries.get(relpath)
        if s is None:
            return None
        if site.recv in ("self", "cls") and caller.cls:
            return self._method_on(relpath, caller.cls, site.name)
        if site.recv == "":
            # enclosing nested FUNCTION scopes, innermost first (a class
            # scope does not make its methods visible as bare names)
            parts = caller.qualname.split(".")
            for i in range(len(parts), 0, -1):
                prefix = ".".join(parts[:i])
                if prefix not in s.functions:
                    continue
                fs = s.functions.get(f"{prefix}.{site.name}")
                if fs is not None:
                    return relpath, fs
            if site.name in s.toplevel:
                return relpath, s.functions[site.name]
            imp = s.imports.get(site.name)
            if imp is not None:
                target_rel = self._resolve_module(relpath, imp[0])
                if target_rel is not None:
                    t = self.summaries[target_rel]
                    if imp[1] in t.toplevel:
                        return target_rel, t.functions[imp[1]]
            # bare ClassName(...) -> its __init__ (a constructor doing
            # blocking I/O blocks the caller just the same)
            if site.name in s.classes:
                fs = s.functions.get(f"{site.name}.__init__")
                if fs is not None:
                    return relpath, fs
            return None
        if "." not in site.recv and site.recv in s.import_mods:
            target_rel = self._resolve_module(relpath,
                                              s.import_mods[site.recv])
            if target_rel is not None:
                t = self.summaries[target_rel]
                if site.name in t.toplevel:
                    return target_rel, t.functions[site.name]
        return None

    # -- transitive blocking ----------------------------------------------

    def blocking_chain(self, relpath: str,
                       fs: FuncSummary) -> Optional[List[str]]:
        """If `fs` can block, a human-readable chain ending at a blocking
        primitive: ['helper (a.py:10)', 'time.sleep() (b.py:7)']. None if
        no blocking call is reachable. Async callees don't count (calling
        them just builds a coroutine)."""
        key = (relpath, fs.qualname)
        if key in self._block_memo:
            return self._block_memo[key]
        self._block_memo[key] = None  # cycle guard: in-progress = no
        chain: Optional[List[str]] = None
        if fs.blocking:
            desc, line = fs.blocking[0]
            chain = [f"{desc} ({relpath}:{line})"]
        else:
            for site in fs.calls:
                if site.awaited or site.poll:
                    continue
                hit = self.resolve(relpath, fs, site)
                if hit is None:
                    continue
                crel, callee = hit
                if callee.is_async or callee.is_generator:
                    continue
                sub = self.blocking_chain(crel, callee)
                if sub is not None:
                    chain = [f"{callee.qualname}() "
                             f"({relpath}:{site.line})"] + sub
                    break
        self._block_memo[key] = chain
        return chain

    # -- lock-order --------------------------------------------------------

    def global_lock(self, relpath: str, fs: FuncSummary, token: str) -> str:
        """Module-local lock token -> project-wide lock identity. `self.X`
        is class-scoped (every instance shares the ordering discipline);
        anything else is module-scoped text."""
        if token.startswith("self.") and fs.cls:
            return f"{relpath}:{fs.cls}.{token[5:]}"
        if token.startswith("cls.") and fs.cls:
            return f"{relpath}:{fs.cls}.{token[4:]}"
        return f"{relpath}:{token}"

    def acquired_locks(self, relpath: str,
                       fs: FuncSummary) -> Dict[str, List[str]]:
        """Locks `fs` may acquire (directly or via resolvable callees):
        {global lock id: acquisition chain description}."""
        key = (relpath, fs.qualname)
        if key in self._lock_memo:
            return self._lock_memo[key]
        self._lock_memo[key] = {}  # cycle guard
        out: Dict[str, List[str]] = {}
        for tok, line, _held in fs.acquires:
            gid = self.global_lock(relpath, fs, tok)
            out.setdefault(gid, [f"with {tok} in {fs.qualname} "
                                 f"({relpath}:{line})"])
        for site in fs.calls:
            hit = self.resolve(relpath, fs, site)
            if hit is None:
                continue
            crel, callee = hit
            for gid, chain in self.acquired_locks(crel, callee).items():
                out.setdefault(
                    gid, [f"{callee.qualname}() ({relpath}:{site.line})"]
                    + chain)
        self._lock_memo[key] = out
        return out


class Project:
    """What `Checker.finish` sees: every module's summary, the shared call
    graph, and each facts-collecting checker's per-module facts."""

    def __init__(self, summaries: Dict[str, ModuleSummary],
                 facts: Dict[str, Dict[str, object]]):
        self.summaries = summaries
        self.graph = CallGraph(summaries)
        self._facts = facts

    def facts(self, name: str) -> Dict[str, object]:
        return self._facts.get(name, {})


# --------------------------------------------------------------------- cache


def suite_digest() -> str:
    """Hash of every graft_check source file — the cache auto-invalidates
    when any checker (or this framework) changes."""
    h = hashlib.sha256()
    pkg = os.path.dirname(os.path.abspath(__file__))
    for dirpath, dirs, files in os.walk(pkg):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for fname in sorted(files):
            if fname.endswith(".py"):
                with open(os.path.join(dirpath, fname), "rb") as f:
                    h.update(f.read())
    return h.hexdigest()


class AnalysisCache:
    """On-disk per-file cache keyed by (path, mtime, size): stores each
    file's per-module findings, collected facts, and call-graph summary,
    so an unchanged file costs one stat — no parse, no AST walk. A digest
    of the graft_check sources guards against stale checker logic."""

    def __init__(self, path: str):
        self.path = path
        self.digest = suite_digest()
        self._dirty = False
        self._files: Dict[str, dict] = {}
        try:
            with open(path, "rb") as f:
                data = pickle.load(f)
            if data.get("digest") == self.digest:
                self._files = data["files"]
        except Exception:  # noqa: BLE001 — missing/corrupt cache: rebuild
            pass
        self._seen: Set[str] = set()

    def lookup(self, relpath: str, st: os.stat_result) -> Optional[dict]:
        self._seen.add(relpath)
        rec = self._files.get(relpath)
        if rec and rec["mtime"] == st.st_mtime_ns and \
                rec["size"] == st.st_size:
            return rec
        return None

    def store(self, relpath: str, st: os.stat_result, findings, facts,
              summary) -> None:
        self._seen.add(relpath)
        self._files[relpath] = {
            "mtime": st.st_mtime_ns, "size": st.st_size,
            "findings": findings, "facts": facts, "summary": summary}
        self._dirty = True

    def save(self) -> None:
        stale = set(self._files) - self._seen
        if stale:
            for rel in stale:
                del self._files[rel]
            self._dirty = True
        if not self._dirty:
            return
        tmp = f"{self.path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                pickle.dump({"digest": self.digest, "files": self._files},
                            f, protocol=pickle.HIGHEST_PROTOCOL)
            os.replace(tmp, self.path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass


# ------------------------------------------------------------------ baseline


@dataclasses.dataclass
class BaselineEntry:
    check_id: str
    path: str
    symbol: str
    justification: str
    line: int  # line in the baseline file (for stale reports)
    count: Optional[int] = None  # exact expected finding count (None = any)

    @property
    def key(self) -> Tuple[str, str, str]:
        return (self.check_id, self.path, self.symbol)


def load_baseline(path: str) -> List[BaselineEntry]:
    """Parse the suppression file. Format, one entry per line:

        <check-id>  <relpath>  <symbol>  [=N]  # one-line justification

    The justification is REQUIRED — an unexplained suppression is a parse
    error, not a suppression. The optional `=N` pins the EXACT number of
    findings the entry covers: without it a single suppression would
    silently swallow every future violation of that check in that
    function; with it, a new violation at an already-baselined symbol
    overflows the count and fails the suite."""
    entries: List[BaselineEntry] = []
    if not os.path.exists(path):
        return entries
    with open(path, encoding="utf-8") as f:
        for lineno, raw in enumerate(f, 1):
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            body, _, justification = line.partition("#")
            fields = body.split()
            count: Optional[int] = None
            if len(fields) == 4 and re.fullmatch(r"=\d+", fields[3]):
                count = int(fields[3][1:])
                fields = fields[:3]
            if len(fields) != 3 or not justification.strip():
                raise ValueError(
                    f"{path}:{lineno}: malformed baseline entry (want "
                    f"'<check-id> <relpath> <symbol> [=N] # justification')"
                    f": {line!r}")
            entries.append(BaselineEntry(fields[0], fields[1], fields[2],
                                         justification.strip(), lineno,
                                         count=count))
    return entries


# -------------------------------------------------------------------- runner


@dataclasses.dataclass
class Report:
    findings: List[Finding]          # unsuppressed (incl. stale-baseline)
    suppressed: List[Finding]        # matched a baseline entry
    parse_errors: List[Finding]

    @property
    def ok(self) -> bool:
        return not self.findings and not self.parse_errors


def iter_py_files(root: str) -> Iterable[str]:
    for dirpath, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for fname in sorted(files):
            if fname.endswith(".py"):
                yield os.path.join(dirpath, fname)


def run_checks(root: str, checkers: Sequence[Checker],
               baseline: Sequence[BaselineEntry] = (),
               baseline_path: str = "",
               scope: Optional[Sequence[str]] = None,
               cache_path: str = "") -> Report:
    """Run every checker over every .py file under `root` (one parse per
    file — or zero, on an AnalysisCache hit), apply the baseline, and
    report stale suppressions as findings.

    `scope`: iterable of relpaths (e.g. the git-changed set) — the call
    graph and tree-wide facts are still built over the WHOLE tree, but
    reported findings are filtered to the scoped files. `cache_path`:
    enables the on-disk (path, mtime, size)-keyed analysis cache; only
    valid for a fixed checker configuration (the default suite)."""
    cache = AnalysisCache(cache_path) if cache_path else None
    findings: List[Finding] = []
    parse_errors: List[Finding] = []
    facts: Dict[str, Dict[str, object]] = {}
    summaries: Dict[str, ModuleSummary] = {}
    for path in iter_py_files(root):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        rec = None
        if cache is not None:
            try:
                st = os.stat(path)
            except OSError:
                continue
            rec = cache.lookup(rel, st)
        if rec is None:
            try:
                mod = ParsedModule(root, path)
            except (SyntaxError, UnicodeDecodeError) as e:
                parse_errors.append(Finding(
                    "parse-error", rel, getattr(e, "lineno", 0) or 0,
                    "<module>", f"cannot parse: {e}"))
                continue
            mod_findings: List[Finding] = []
            mod_facts: Dict[str, object] = {}
            for checker in checkers:
                mod_findings.extend(checker.check_module(mod))
                if checker.facts_name is not None:
                    mod_facts[checker.facts_name] = checker.collect(mod)
            summary = summarize_module(mod)
            if cache is not None:
                cache.store(rel, st, mod_findings, mod_facts, summary)
            rec = {"findings": mod_findings, "facts": mod_facts,
                   "summary": summary}
        findings.extend(rec["findings"])
        for name, f in rec["facts"].items():
            facts.setdefault(name, {})[rel] = f
        summaries[rel] = rec["summary"]
    if cache is not None:
        cache.save()
    project = Project(summaries, facts)
    for checker in checkers:
        findings.extend(checker.finish(project))

    by_key: dict = {}
    for entry in baseline:
        by_key.setdefault(entry.key, []).append(entry)
    unsuppressed: List[Finding] = []
    suppressed: List[Finding] = []
    matched: dict = {}
    for f in findings:
        if f.key in by_key:
            matched[f.key] = matched.get(f.key, 0) + 1
            suppressed.append(f)
        else:
            unsuppressed.append(f)
    scope_set = None if scope is None else {
        s.replace(os.sep, "/") for s in scope}
    if scope_set is not None:
        # parse errors are NEVER scoped out: an unparsable file anywhere
        # silently voids the tree-wide analysis (its dispatch arms, locks
        # and facts are missing), so a --changed run must still fail loud
        unsuppressed = [f for f in unsuppressed if f.path in scope_set]
    bl_rel = baseline_path or "tools/graft_check/baseline.txt"
    for entry in baseline:
        if scope_set is not None and entry.path not in scope_set:
            continue  # --changed: only judge staleness for scoped files
        n = matched.get(entry.key, 0)
        if n == 0:
            unsuppressed.append(Finding(
                "stale-baseline", bl_rel, entry.line,
                "<baseline>",
                f"suppression {entry.check_id} {entry.path} {entry.symbol} "
                f"no longer matches any finding — delete it"))
        elif entry.count is not None and n != entry.count:
            # a count overflow means a NEW violation is hiding behind an
            # old justification; an underflow means some were fixed and
            # the pin must shrink with them
            unsuppressed.append(Finding(
                "stale-baseline", bl_rel, entry.line,
                "<baseline>",
                f"suppression {entry.check_id} {entry.path} {entry.symbol} "
                f"is pinned to ={entry.count} finding(s) but matched {n} — "
                f"{'a new violation hides behind it' if n > entry.count else 'update the pin'}"))
    unsuppressed.sort(key=lambda f: (f.path, f.line, f.check_id))
    return Report(unsuppressed, suppressed, parse_errors)
