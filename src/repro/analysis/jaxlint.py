"""jaxlint — AST checks for this repo's JAX/Pallas discipline.

Every check exists because a PR in this repo's history fixed (or nearly
shipped) the corresponding bug class by hand; see ``CHECKS`` for the
catalogue. The analyzer is intentionally repo-tuned, not general: the hot
path set, the blessed bucketing helpers, and the Pallas test
cross-reference all name structures of THIS codebase (``LintConfig``).

Design notes:

* Analysis is per-module and flow-approximate: statements are ordered by
  source position, so a read *lexically after* a donating dispatch counts
  as after it even across branches. That over-approximation is the right
  polarity for a linter (false positives are suppressible; misses are not
  visible), with one deliberate blind spot — a donation at the bottom of a
  loop body followed by a read at the top of the next iteration is not
  seen. Rebinding the donated name in the dispatch statement itself (the
  idiom ``logits, self.cache = self._decode(self.params, self.cache, …)``)
  is recognized and never flagged.
* Suppression: a finding is dropped when its line (or an immediately
  preceding comment-only line run) carries
  ``# jaxlint: disable=<check>[,<check>…]`` (or ``disable=all``), with an
  optional ``-- reason`` tail. Prefer inline suppression for
  intentional-by-design sites (self-documenting); use the baseline file
  (``repro.analysis.baseline``) for bulk-accepted legacy findings.
* Fingerprints are line-number-free: ``md5(check|path|qualname|stripped
  source line|occurrence)``. Baselines survive unrelated edits but go
  stale when the flagged line itself changes — by design, an edited line
  must re-justify its baseline entry.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

# check name -> one-line contract (shown by --list-checks)
CHECKS: Dict[str, str] = {
    "donated-use": (
        "a buffer passed at a donate_argnums position of a jit'd call is "
        "read again before being rebound (use-after-dispatch)"),
    "host-sync": (
        "host-device synchronization (.item(), np.asarray/np.array on "
        "device values, block_until_ready, int()/float() on indexed "
        "values) inside a configured hot-path function"),
    "retrace": (
        "a jit'd callee is fed an array sliced to a Python-varying extent "
        "outside the blessed bucketing helpers — every distinct extent "
        "retraces"),
    "pallas-grid": (
        "a pl.pallas_call grid / BlockSpec dimension is a bare magic "
        "number instead of a named constant (0 and 1 are allowed)"),
    "pallas-test": (
        "a public Pallas kernel wrapper lacks an interpret= parameter or "
        "is never referenced by any file under tests/ (no interpret-mode "
        "equivalence coverage)"),
    "traced-flow": (
        "a jit-traced function body branches on (or concretizes with "
        "int/float/bool) a traced parameter — TracerBoolConversionError "
        "or silent host fallback at trace time"),
}

_JIT_NAMES = {"jax.jit", "jit"}
_PARTIAL_NAMES = {"functools.partial", "partial"}
_SYNC_CALLS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
               "jax.device_get"}
_SYNC_ATTRS = {"item", "block_until_ready", "copy_to_host_async"}
_HOST_LITERALS = (ast.List, ast.ListComp, ast.Tuple, ast.GeneratorExp,
                  ast.Dict, ast.DictComp, ast.Constant)

_SUPPRESS_RE = re.compile(r"#\s*jaxlint:\s*disable=([\w\-,]+)")


@dataclasses.dataclass(frozen=True)
class Finding:
    check: str
    path: str              # as reported (display)
    line: int
    col: int
    qualname: str
    message: str
    fingerprint: str

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.check} "
                f"[{self.fingerprint}] {self.qualname}: {self.message}")


@dataclasses.dataclass
class LintConfig:
    """Repo-tuned knobs. Defaults encode THIS codebase's conventions."""
    # qualname regexes whose bodies are hot scheduling/dispatch paths —
    # host syncs here stall the engine's per-step pipeline
    hot_functions: Tuple[str, ...] = (
        r"^Engine\.step$",
        r"^Engine\._ensure_grow$",
        r"^Engine\._advance_pending$",
        r"^Engine\._finish_pending$",
        r"^Engine\._admit_group$",
        r"^Engine\._admit_group_suffix$",
        r"^Engine\._sample$",
        r"^Engine\._scatter_group$",
        r"^Engine\._preempt$",
        r"^Engine\.export_kv$",
        r"^Engine\.export_live_kv$",
    )
    # qualname regexes blessed to feed jit'd callees shape-varying data —
    # the power-of-2 bucketing helpers pad before dispatch
    blessed_retrace: Tuple[str, ...] = (
        r"^Engine\._admit_group$",
        r"^Engine\._admit_group_suffix$",
        r"^Engine\._bucket$",
        r"^Engine\.bucket_lens$",
    )
    # directory whose files provide the pallas-test cross-reference
    tests_dir: Optional[str] = None
    enabled: Tuple[str, ...] = tuple(CHECKS)
    grid_allowed_ints: Tuple[int, ...] = (0, 1)


# -- small AST helpers ----------------------------------------------------------
def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.jit' / 'self._decode' / 'np.asarray' for Name/Attribute
    chains; None for anything else (calls, subscripts…)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _const_ints(node: ast.AST) -> Tuple[int, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
        return tuple(out)
    return ()


def _const_strs(node: ast.AST) -> Tuple[str, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(e.value for e in node.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, str))
    return ()


def _walk_local(fn: ast.AST) -> Iterable[ast.AST]:
    """Nodes of ``fn``'s own body, NOT descending into nested function or
    class definitions — those are visited separately under their own
    qualname (so per-function policy like hot/blessed applies to the
    innermost enclosing function, and nothing is analyzed twice)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
            stack.extend(ast.iter_child_nodes(node))


def _stmts_in_order(fn: ast.AST) -> List[ast.stmt]:
    """``fn``'s own statement nodes in source order (flow-approximate
    linearization; see module docstring)."""
    out = [n for n in _walk_local(fn) if isinstance(n, ast.stmt)]
    out.sort(key=lambda n: (n.lineno, n.col_offset))
    return out


def _parents(root: ast.AST) -> Dict[ast.AST, ast.AST]:
    par: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(root):
        for child in ast.iter_child_nodes(node):
            par[child] = node
    return par


@dataclasses.dataclass
class _JitInfo:
    name: str                       # call-site dotted name
    donate: Tuple[int, ...] = ()
    static_nums: Tuple[int, ...] = ()
    static_names: Tuple[str, ...] = ()
    func_name: Optional[str] = None  # wrapped python function, if a Name


def _jit_kwargs(call: ast.Call) -> Dict[str, Tuple]:
    out: Dict[str, Tuple] = {}
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            out["donate"] = _const_ints(kw.value)
        elif kw.arg == "static_argnums":
            out["static_nums"] = _const_ints(kw.value)
        elif kw.arg == "static_argnames":
            out["static_names"] = _const_strs(kw.value)
    return out


def _collect_jit_registry(tree: ast.Module) -> Dict[str, _JitInfo]:
    """Map call-site dotted names -> jit metadata.

    Recognizes ``X = jax.jit(f, …)`` (X a Name or self-attribute),
    ``@jax.jit`` and ``@functools.partial(jax.jit, …)`` decorations.
    """
    reg: Dict[str, _JitInfo] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            call = node.value
            if _dotted(call.func) not in _JIT_NAMES:
                continue
            kw = _jit_kwargs(call)
            fn = None
            if call.args and isinstance(call.args[0], ast.Name):
                fn = call.args[0].id
            for tgt in node.targets:
                name = _dotted(tgt)
                if name is not None:
                    reg[name] = _JitInfo(name=name, func_name=fn, **kw)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _dotted(dec) in _JIT_NAMES:
                    reg[node.name] = _JitInfo(name=node.name,
                                              func_name=node.name)
                elif isinstance(dec, ast.Call):
                    head = _dotted(dec.func)
                    if head in _JIT_NAMES:
                        reg[node.name] = _JitInfo(
                            name=node.name, func_name=node.name,
                            **_jit_kwargs(dec))
                    elif (head in _PARTIAL_NAMES and dec.args
                          and _dotted(dec.args[0]) in _JIT_NAMES):
                        reg[node.name] = _JitInfo(
                            name=node.name, func_name=node.name,
                            **_jit_kwargs(dec))
    return reg


def _suppressions(source: str) -> Dict[int, Set[str]]:
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(line)
        if m:
            out[i] = set(m.group(1).split(","))
    return out


def _suppressed(check: str, line: int, sup: Dict[int, Set[str]],
                lines: Sequence[str]) -> bool:
    """Suppressed if the finding's line, or the run of comment-only lines
    immediately above it, carries a matching disable."""
    def hit(ln: int) -> bool:
        s = sup.get(ln)
        return s is not None and (check in s or "all" in s)

    if hit(line):
        return True
    ln = line - 1
    while ln >= 1 and ln <= len(lines) and lines[ln - 1].lstrip().startswith("#"):
        if hit(ln):
            return True
        ln -= 1
    return False


class _Scoped(ast.NodeVisitor):
    """Base visitor that tracks class/function qualnames."""

    def __init__(self) -> None:
        self._stack: List[str] = []

    @property
    def qualname(self) -> str:
        return ".".join(self._stack)

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    def _visit_fn(self, node) -> None:
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn


# -- per-module analysis --------------------------------------------------------
class _ModuleLinter:
    def __init__(self, path: str, rel: str, source: str,
                 config: LintConfig, tests_blob: str) -> None:
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.config = config
        self.tests_blob = tests_blob
        self.tree = ast.parse(source, filename=path)
        self.registry = _collect_jit_registry(self.tree)
        self.sup = _suppressions(source)
        self.raw: List[Tuple[str, int, int, str, str]] = []
        self._hot = [re.compile(p) for p in config.hot_functions]
        self._blessed = [re.compile(p) for p in config.blessed_retrace]

    # -- emit helpers ------------------------------------------------------
    def _emit(self, check: str, node: ast.AST, qualname: str,
              message: str) -> None:
        if check not in self.config.enabled:
            return
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        if _suppressed(check, line, self.sup, self.lines):
            return
        self.raw.append((check, line, col, qualname, message))

    def findings(self) -> List[Finding]:
        seen: Dict[Tuple[str, str, str], int] = {}
        out: List[Finding] = []
        for check, line, col, qualname, message in sorted(self.raw):
            src = (self.lines[line - 1].strip()
                   if 0 < line <= len(self.lines) else "")
            key = (check, qualname, src)
            occ = seen.get(key, 0)
            seen[key] = occ + 1
            fp = hashlib.md5(
                f"{check}|{self.rel}|{qualname}|{src}|{occ}"
                .encode()).hexdigest()[:16]
            out.append(Finding(check, self.rel, line, col, qualname,
                               message, fp))
        return out

    def run(self) -> List[Finding]:
        self._walk_functions()
        self._check_pallas_grid()
        self._check_pallas_test()
        self._check_traced_flow()
        return self.findings()

    # -- function-scoped checks -------------------------------------------
    def _walk_functions(self) -> None:
        linter = self

        class V(_Scoped):
            def _visit_fn(self, node) -> None:
                self._stack.append(node.name)
                qn = self.qualname
                linter._check_donated_use(node, qn)
                if any(r.search(qn) for r in linter._hot):
                    linter._check_host_sync(node, qn)
                if not any(r.search(qn) for r in linter._blessed):
                    linter._check_retrace(node, qn)
                self.generic_visit(node)
                self._stack.pop()

            visit_FunctionDef = _visit_fn
            visit_AsyncFunctionDef = _visit_fn

        V().visit(self.tree)

    # donated-use ----------------------------------------------------------
    def _check_donated_use(self, fn, qualname: str) -> None:
        stmts = [s for s in fn.body]
        # direct statements only at top; nested bodies handled by the
        # source-order linearization below
        all_stmts = _stmts_in_order(fn)
        par = _parents(fn)

        def stmt_of(node: ast.AST) -> Optional[ast.stmt]:
            while node in par and not isinstance(node, ast.stmt):
                node = par[node]
            return node if isinstance(node, ast.stmt) else None

        def assign_targets(stmt: ast.stmt) -> Set[str]:
            tgts: Set[str] = set()
            if isinstance(stmt, ast.Assign):
                nodes = stmt.targets
            elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                nodes = [stmt.target]
            else:
                return tgts
            for t in nodes:
                for sub in ast.walk(t):
                    d = _dotted(sub)
                    if d is not None and isinstance(
                            getattr(sub, "ctx", None), ast.Store):
                        tgts.add(d)
            return tgts

        def loads_of(node: ast.AST, dotted: str) -> List[ast.AST]:
            hits = []
            for sub in ast.walk(node):
                if (_dotted(sub) == dotted
                        and isinstance(getattr(sub, "ctx", None), ast.Load)
                        # the value side of a dotted chain repeats; only
                        # count the full chain's outermost node
                        and not (sub in par
                                 and isinstance(par[sub], ast.Attribute))):
                    hits.append(sub)
            return hits

        del stmts
        for node in _walk_local(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func)
            info = self.registry.get(callee) if callee else None
            if info is None or not info.donate:
                continue
            stmt = stmt_of(node)
            if stmt is None:
                continue
            for pos in info.donate:
                if pos >= len(node.args):
                    continue
                if any(isinstance(a, ast.Starred)
                       for a in node.args[:pos + 1]):
                    continue
                donated = _dotted(node.args[pos])
                if donated is None:
                    continue
                rebound = donated in assign_targets(stmt)
                # reads of the donated name in the SAME statement beyond
                # the donated argument itself (e.g. ``y = f(x) + x``)
                arg_reads = len(loads_of(node.args[pos], donated))
                call_reads = sum(len(loads_of(a, donated))
                                 for a in node.args)
                call_reads += sum(len(loads_of(kw.value, donated))
                                  for kw in node.keywords)
                stmt_reads = len(loads_of(stmt, donated))
                if stmt_reads > call_reads or call_reads > arg_reads:
                    self._emit(
                        "donated-use", node, qualname,
                        f"`{donated}` is donated to `{callee}` (arg {pos}) "
                        f"but read again in the same statement")
                    continue
                if rebound:
                    continue
                end = getattr(stmt, "end_lineno", stmt.lineno)
                for later in all_stmts:
                    if later.lineno <= end:
                        continue
                    reads = loads_of(later, donated)
                    tgts = assign_targets(later)
                    if reads:
                        self._emit(
                            "donated-use", reads[0], qualname,
                            f"`{donated}` was donated to `{callee}` "
                            f"(arg {pos}) at line {stmt.lineno} and is "
                            f"read here before being rebound")
                        break
                    if donated in tgts:
                        break        # rebound: later reads are fine
                else:
                    # fell through without rebind: donated name escapes
                    # the function unread — fine
                    pass

    # host-sync ------------------------------------------------------------
    def _check_host_sync(self, fn, qualname: str) -> None:
        for node in _walk_local(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func)
            if callee in _SYNC_CALLS:
                if node.args and isinstance(node.args[0], _HOST_LITERALS):
                    continue      # pure host construction, no device sync
                self._emit("host-sync", node, qualname,
                           f"`{callee}` pulls a device value to the host "
                           f"inside hot path `{qualname}`")
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in _SYNC_ATTRS and not node.args):
                self._emit("host-sync", node, qualname,
                           f"`.{node.func.attr}()` blocks on the device "
                           f"inside hot path `{qualname}`")
            elif (callee in ("int", "float") and len(node.args) == 1
                  and isinstance(node.args[0], ast.Subscript)):
                self._emit("host-sync", node, qualname,
                           f"`{callee}()` on an indexed value syncs if it "
                           f"is a device array (hot path `{qualname}`)")

    # retrace --------------------------------------------------------------
    def _check_retrace(self, fn, qualname: str) -> None:
        for node in _walk_local(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func)
            info = self.registry.get(callee) if callee else None
            if info is None:
                continue
            for i, arg in enumerate(node.args):
                if i in info.static_nums:
                    continue
                slc = self._varying_slice(arg)
                if slc is not None:
                    self._emit(
                        "retrace", arg, qualname,
                        f"arg {i} of jit'd `{callee}` is sliced to the "
                        f"Python-varying extent `{slc}` — every distinct "
                        f"extent retraces (pad to a bucket, or bless "
                        f"this helper in LintConfig)")

    @staticmethod
    def _varying_slice(arg: ast.AST) -> Optional[str]:
        for sub in ast.walk(arg):
            if not isinstance(sub, ast.Subscript):
                continue
            parts = (sub.slice.elts
                     if isinstance(sub.slice, ast.Tuple) else [sub.slice])
            for p in parts:
                if isinstance(p, ast.Slice):
                    for bound in (p.lower, p.upper):
                        if bound is not None and not isinstance(
                                bound, ast.Constant):
                            try:
                                return ast.unparse(bound)
                            except Exception:
                                return "<expr>"
        return None

    # pallas-grid ----------------------------------------------------------
    def _check_pallas_grid(self) -> None:
        if "pallas_call" not in self.source:
            return
        allowed = set(self.config.grid_allowed_ints)
        linter = self

        class V(_Scoped):
            def visit_Call(self, node: ast.Call) -> None:
                callee = _dotted(node.func) or ""
                if callee.endswith("pallas_call") or \
                        callee.endswith("GridSpec"):
                    for kw in node.keywords:
                        if kw.arg == "grid":
                            linter._flag_magic(kw.value, self.qualname,
                                               "grid", allowed)
                elif callee.endswith("BlockSpec") and node.args:
                    linter._flag_magic(node.args[0], self.qualname,
                                       "BlockSpec block shape", allowed)
                self.generic_visit(node)

        V().visit(self.tree)

    def _flag_magic(self, node: ast.AST, qualname: str, what: str,
                    allowed: Set[int]) -> None:
        if not isinstance(node, (ast.Tuple, ast.List)):
            return
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int) \
                    and e.value not in allowed:
                self._emit(
                    "pallas-grid", e, qualname,
                    f"magic number {e.value} in {what} — tie kernel "
                    f"dims to named constants so grid math stays "
                    f"auditable")

    # pallas-test ----------------------------------------------------------
    def _check_pallas_test(self) -> None:
        if "pallas_call" not in self.source:
            return
        for node in self.tree.body:
            if not isinstance(node, ast.FunctionDef):
                continue
            if node.name.startswith("_"):
                continue
            has_call = any(
                isinstance(n, ast.Call)
                and (_dotted(n.func) or "").endswith("pallas_call")
                for n in ast.walk(node))
            if not has_call:
                continue
            params = {a.arg for a in node.args.args}
            params |= {a.arg for a in node.args.kwonlyargs}
            if "interpret" not in params:
                self._emit(
                    "pallas-test", node, node.name,
                    f"Pallas wrapper `{node.name}` has no interpret= "
                    f"parameter — interpret-mode equivalence tests "
                    f"cannot exercise it")
            if self.tests_blob and not re.search(
                    rf"\b{re.escape(node.name)}\b", self.tests_blob):
                self._emit(
                    "pallas-test", node, node.name,
                    f"Pallas wrapper `{node.name}` is not referenced by "
                    f"any file under the tests directory — add an "
                    f"interpret-mode equivalence test")

    # traced-flow ----------------------------------------------------------
    def _check_traced_flow(self) -> None:
        defs: Dict[str, ast.AST] = {}
        qn: Dict[str, str] = {}
        linter = self

        class Collect(_Scoped):
            def _visit_fn(self, node) -> None:
                self._stack.append(node.name)
                defs.setdefault(node.name, node)
                qn.setdefault(node.name, self.qualname)
                self.generic_visit(node)
                self._stack.pop()

            visit_FunctionDef = _visit_fn
            visit_AsyncFunctionDef = _visit_fn

        Collect().visit(self.tree)
        for info in self.registry.values():
            fn = defs.get(info.func_name or "")
            if fn is None:
                continue
            args = fn.args
            names = [a.arg for a in args.args + args.kwonlyargs]
            traced = {n for i, n in enumerate(names)
                      if i not in info.static_nums
                      and n not in info.static_names and n != "self"}
            linter._traced_flow_body(fn, qn[fn.name], traced)

    def _traced_flow_body(self, fn, qualname: str,
                          traced: Set[str]) -> None:
        def uses_traced(node: ast.AST) -> Optional[str]:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name) and sub.id in traced:
                    return sub.id
            return None

        for node in _walk_local(fn):     # nested defs trace separately
            if isinstance(node, (ast.If, ast.While)):
                test = node.test
                if (isinstance(test, ast.Compare) and all(
                        isinstance(op, (ast.Is, ast.IsNot))
                        for op in test.ops)):
                    continue   # `is (not) None` on optionals is static
                name = uses_traced(test)
                if name is not None:
                    kind = "if" if isinstance(node, ast.If) else "while"
                    self._emit(
                        "traced-flow", node, qualname,
                        f"`{kind}` on traced `{name}` inside jit-traced "
                        f"`{qualname}` — use jnp.where/lax.cond or mark "
                        f"it static")
            elif isinstance(node, ast.Call):
                callee = _dotted(node.func)
                if callee in ("int", "float", "bool") and node.args:
                    name = uses_traced(node.args[0])
                    if name is not None:
                        self._emit(
                            "traced-flow", node, qualname,
                            f"`{callee}()` concretizes traced `{name}` "
                            f"inside jit-traced `{qualname}`")


# -- entry points ---------------------------------------------------------------
def _read_tests_blob(tests_dir: Optional[str]) -> str:
    if not tests_dir or not os.path.isdir(tests_dir):
        return ""
    chunks = []
    for base, _dirs, files in os.walk(tests_dir):
        for f in sorted(files):
            if f.endswith(".py"):
                p = os.path.join(base, f)
                try:
                    with open(p, encoding="utf-8") as fh:
                        chunks.append(fh.read())
                except OSError:
                    pass
    return "\n".join(chunks)


def analyze_file(path: str, config: Optional[LintConfig] = None,
                 rel: Optional[str] = None,
                 tests_blob: Optional[str] = None) -> List[Finding]:
    config = config or LintConfig()
    if tests_blob is None:
        tests_blob = _read_tests_blob(config.tests_dir)
    with open(path, encoding="utf-8") as fh:
        source = fh.read()
    return _ModuleLinter(path, rel or path, source, config,
                         tests_blob).run()


def _iter_py(root: str) -> Iterable[Tuple[str, str]]:
    """(abspath, relpath-for-fingerprints). Fingerprint paths are rooted
    at the scan root's basename so they are stable across machines and
    working directories (``src/repro/…`` whether scanned as ``src/`` or
    ``/abs/path/src``)."""
    root = root.rstrip(os.sep)
    if os.path.isfile(root):
        yield root, os.path.basename(root)
        return
    base = os.path.basename(os.path.abspath(root))
    for dirpath, dirnames, files in os.walk(root):
        dirnames[:] = [d for d in sorted(dirnames)
                       if d not in ("__pycache__", ".git")]
        for f in sorted(files):
            if f.endswith(".py"):
                p = os.path.join(dirpath, f)
                yield p, os.path.join(
                    base, os.path.relpath(p, root)).replace(os.sep, "/")


def analyze_paths(paths: Sequence[str],
                  config: Optional[LintConfig] = None) -> List[Finding]:
    config = config or LintConfig()
    tests_blob = _read_tests_blob(config.tests_dir)
    out: List[Finding] = []
    for root in paths:
        for path, rel in _iter_py(root):
            out.extend(analyze_file(path, config, rel=rel,
                                    tests_blob=tests_blob))
    return out
