"""Core model for graftlint: parsed-module cache, findings, suppression.

Everything here is plain stdlib ``ast`` — parsing happens once per file
and every rule visits the same tree (the "shared parsed-module cache"
that keeps a 6-rule sweep of ~200 files under a second).
"""
import ast
import hashlib
import re
import tokenize
from dataclasses import dataclass, field
from io import StringIO
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

# `# noqa`, `# noqa: CODE`, `# noqa: CODE1,CODE2 — free-form reason`.
# The em-dash (or ` - `) reason tail is the repo's existing BLE001 style.
_NOQA_RE = re.compile(
    r"#\s*noqa(?::\s*(?P<codes>[A-Za-z0-9_\-]+(?:\s*,\s*[A-Za-z0-9_\-]+)*))?",
)


@dataclass(frozen=True)
class Finding:
    """One rule violation at one source location."""

    rule: str           # rule name, e.g. "SWALLOWED-API"
    path: str           # posix path relative to the analysis root
    line: int           # 1-based line of the offending statement
    message: str        # human-readable description of the hazard
    snippet: str = ""   # stripped source of the flagged line
    occurrence: int = 0  # index among identical (rule, path, snippet) hits

    @property
    def fingerprint(self) -> str:
        """Stable identity for baseline matching.

        Deliberately excludes the line number so unrelated edits above a
        baselined site don't invalidate the entry; includes the message
        so two findings anchored on one line (e.g. two missing cache-key
        parameters) baseline independently; the occurrence index
        disambiguates exact duplicates within one file.
        """
        raw = "\x1f".join([self.rule, self.path, self.snippet,
                           self.message, str(self.occurrence)])
        return hashlib.sha1(raw.encode("utf-8")).hexdigest()[:16]

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule}: {self.message}"

    def to_json(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "message": self.message,
            "snippet": self.snippet,
            "fingerprint": self.fingerprint,
        }


class ParsedModule:
    """One source file parsed once: tree, lines, noqa map, jax aliases."""

    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.lines: List[str] = source.splitlines()
        self.tree: ast.AST = ast.parse(source, filename=path)
        self._noqa: Optional[Dict[int, Optional[Set[str]]]] = None
        self._noqa_reasons: Dict[int, str] = {}
        self._jax_aliases: Optional[Set[str]] = None
        self._nodes: Optional[List[ast.AST]] = None
        self._children: Dict[ast.AST, List[ast.AST]] = {}
        self._walks: Dict[ast.AST, List[ast.AST]] = {}

    def nodes(self) -> List[ast.AST]:
        """Every AST node, in ``ast.walk`` order, computed once — a
        full sweep runs ~10 rules over each module and a fresh walk per
        rule is the single biggest cost of the whole sweep. The one
        pass that reads the tree's fields also keeps each node's child
        list, which `children` hands out and `walk` reads."""
        if self._nodes is None:
            children = self._children
            order = [self.tree]
            for node in order:  # grows as it is read: breadth first
                # ast.iter_child_nodes, without its two generators
                kids = children[node] = []
                for name in node._fields:
                    value = getattr(node, name, None)
                    if isinstance(value, list):
                        for item in value:
                            if isinstance(item, ast.AST):
                                kids.append(item)
                    elif isinstance(value, ast.AST):
                        kids.append(value)
                order.extend(kids)
            self._nodes = order
        return self._nodes

    def children(self) -> Dict[ast.AST, List[ast.AST]]:
        """Each node's ``ast.iter_child_nodes``, as a list."""
        self.nodes()
        return self._children

    def walk(self, node: ast.AST) -> List[ast.AST]:
        """``ast.walk(node)`` for a node of this tree, as a list kept
        per root: several rules walk the same function."""
        found = self._walks.get(node)
        if found is None:
            children = self.children()
            found = self._walks[node] = [node]
            for sub in found:
                found.extend(children[sub])
        return found

    # -- suppression -------------------------------------------------------
    @property
    def noqa(self) -> Dict[int, Optional[Set[str]]]:
        """line -> set of suppressed codes (None = blanket ``# noqa``).

        Comments are read with tokenize so a ``# noqa`` inside a string
        literal never suppresses anything.
        """
        if self._noqa is None:
            self._noqa = {}
            try:
                toks = tokenize.generate_tokens(StringIO(self.source).readline)
                for tok in toks:
                    if tok.type != tokenize.COMMENT:
                        continue
                    m = _NOQA_RE.search(tok.string)
                    if not m:
                        continue
                    tail = tok.string[m.end():].strip()
                    tail = tail.lstrip("—-–: ").strip()
                    prev_tail = self._noqa_reasons.get(tok.start[0], "")
                    self._noqa_reasons[tok.start[0]] = prev_tail or tail
                    codes = m.group("codes")
                    if codes is None:
                        self._noqa[tok.start[0]] = None
                    else:
                        parsed = {c.strip().upper()
                                  for c in codes.split(",") if c.strip()}
                        prev = self._noqa.get(tok.start[0])
                        if prev is None and tok.start[0] in self._noqa:
                            pass  # blanket noqa already covers the line
                        else:
                            merged = (prev or set()) | parsed
                            self._noqa[tok.start[0]] = merged
            except tokenize.TokenError:
                pass  # ast.parse succeeded; partial comment map is fine
        return self._noqa

    def is_suppressed(self, line: int, codes: Sequence[str]) -> bool:
        """True when `line` carries a noqa naming any of `codes` (or a
        blanket one). Multi-line statements: the anchor line only —
        suppressions live where the finding points."""
        entry = self.noqa.get(line, ...)
        if entry is ...:
            return False
        if entry is None:
            return True
        wanted = {c.upper() for c in codes}
        return bool(entry & wanted)

    def noqa_reason(self, line: int) -> Optional[str]:
        """The free-form reason tail of the noqa on `line`: None when
        the line carries no noqa at all, "" when it carries a bare or
        reasonless one. Rules that *mandate* reasoned suppressions
        (COLLECTIVE-MESH's check_vma=False contract) distinguish the
        two: a reasonless noqa is itself the finding."""
        self.noqa  # force the tokenize pass
        if line not in (self._noqa or {}):
            return None
        return self._noqa_reasons.get(line, "")

    # -- jax alias tracking ------------------------------------------------
    @property
    def jax_aliases(self) -> Set[str]:
        """Local names bound to jax modules/objects, anywhere in the file
        (function-local ``import jax.profiler as jp`` included): the roots
        a call chain may start from and still be "a jax API call"."""
        if self._jax_aliases is None:
            names: Set[str] = {"jax", "lax"}
            for node in self.nodes():
                if isinstance(node, ast.Import):
                    for a in node.names:
                        if a.name == "jax" or a.name.startswith("jax."):
                            names.add((a.asname or a.name).split(".")[0])
                elif isinstance(node, ast.ImportFrom):
                    mod = node.module or ""
                    if mod == "jax" or mod.startswith("jax."):
                        for a in node.names:
                            names.add(a.asname or a.name)
            self._jax_aliases = names
        return self._jax_aliases

    def line_text(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""


class ModuleCache:
    """Parse each file exactly once; every rule shares the result."""

    def __init__(self) -> None:
        self._modules: Dict[str, ParsedModule] = {}
        self.errors: Dict[str, str] = {}  # path -> parse error (reported)

    def parse_file(self, filename: str, rel_path: str) -> Optional[ParsedModule]:
        mod = self._modules.get(rel_path)
        if mod is not None:
            return mod
        if rel_path in self.errors:
            return None
        try:
            with tokenize.open(filename) as f:  # honors coding cookies
                source = f.read()
            mod = ParsedModule(rel_path, source)
        except (OSError, SyntaxError, ValueError) as e:
            self.errors[rel_path] = f"{type(e).__name__}: {e}"
            return None
        self._modules[rel_path] = mod
        return mod

    def parse_source(self, source: str, rel_path: str = "<memory>") -> ParsedModule:
        mod = self._modules.get(rel_path)
        if mod is None:
            mod = ParsedModule(rel_path, source)
            self._modules[rel_path] = mod
        return mod


class Rule:
    """Base class: one hazard class, one AST visitor.

    Subclasses set `name` (the finding code), optional `aliases`
    (extra accepted noqa codes, e.g. BLE001), and implement `check`.
    """

    name: str = ""
    aliases: Tuple[str, ...] = ()
    description: str = ""

    @property
    def codes(self) -> Tuple[str, ...]:
        return (self.name,) + self.aliases

    def check(self, module: ParsedModule) -> Iterator[Finding]:
        raise NotImplementedError

    def project_check(self, module: ParsedModule,
                      project) -> Iterator[Finding]:
        """v2 entry point: like `check` but with the whole Project
        (parsed-module set + call graph, see callgraph.Project) in
        scope. The runner always calls this; the default delegates so
        single-module rules never notice. `project` is untyped here
        only to keep core.py import-free of callgraph.py."""
        return self.check(module)

    # -- helpers for subclasses -------------------------------------------
    def findings(self, module: ParsedModule,
                 hits: Iterable[Tuple[int, str]]) -> Iterator[Finding]:
        """Materialize (line, message) hits: attach snippets, assign
        occurrence indices, and drop inline-suppressed ones."""
        seen: Dict[Tuple[str, str], int] = {}
        for line, message in hits:
            snippet = module.line_text(line)
            occ = seen.get((snippet, message), 0)
            seen[(snippet, message)] = occ + 1
            if module.is_suppressed(line, self.codes):
                continue
            yield Finding(rule=self.name, path=module.path, line=line,
                          message=message, snippet=snippet, occurrence=occ)


# -- shared AST utilities ---------------------------------------------------

def dotted_chain(node: ast.AST) -> Optional[List[str]]:
    """`jax.lax.axis_size` -> ["jax", "lax", "axis_size"]; None when the
    expression is not a plain Name/Attribute chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        parts.reverse()
        return parts
    return None


def call_chain(call: ast.Call) -> Optional[List[str]]:
    return dotted_chain(call.func)


def walk_stmts(module: ParsedModule,
               body: Sequence[ast.stmt]) -> Iterator[ast.AST]:
    """ast.walk over a statement list (a Try body without its handlers)."""
    for stmt in body:
        yield from module.walk(stmt)


def is_jax_call(call: ast.Call, aliases: Set[str]) -> bool:
    chain = call_chain(call)
    return chain is not None and chain[0] in aliases


@dataclass
class FunctionInfo:
    """Lightweight record of a function and how it gets traced/jitted."""

    node: ast.AST  # FunctionDef | AsyncFunctionDef | Lambda
    name: str
    parent: Optional[ast.AST]
    traced_via: str = ""  # "" if not traced; else "decorator" / "jit-call" / ...


_JIT_DECORATORS = {("jit",), ("jax", "jit")}
_TRACE_ENTRY_TAILS = {
    "jit", "scan", "while_loop", "fori_loop", "cond", "switch",
    "vmap", "pmap", "grad", "value_and_grad", "shard_map", "pallas_call",
    "checkpoint", "remat",
}


def _decorator_is_jit(dec: ast.AST) -> bool:
    chain = dotted_chain(dec)
    if chain is not None:
        return tuple(chain) in _JIT_DECORATORS
    if isinstance(dec, ast.Call):
        # @partial(jax.jit, ...) / @functools.partial(jit, ...)
        fchain = dotted_chain(dec.func)
        if fchain is not None and fchain[-1] == "partial" and dec.args:
            inner = dotted_chain(dec.args[0])
            return inner is not None and tuple(inner) in _JIT_DECORATORS
        # @jax.jit(...) with options
        fc = dotted_chain(dec.func)
        return fc is not None and tuple(fc) in _JIT_DECORATORS
    return False


def traced_functions(module: ParsedModule) -> List[FunctionInfo]:
    """Functions that get traced by jax: jit-decorated, or defined and
    then passed (by name or inline) to a trace entry point like
    jax.jit / lax.scan / shard_map within the enclosing scope.

    Memoized per module (several rules ask; the parent map alone is an
    O(module) walk)."""
    cached = getattr(module, "_traced_functions", None)
    if cached is not None:
        return list(cached)
    out: List[FunctionInfo] = []
    # one walk collects everything (parent edges, defs, calls) — the
    # tree is visited once, not three times
    parents: Dict[ast.AST, ast.AST] = {}
    all_defs: List[ast.AST] = []
    calls: List[ast.Call] = []
    children = module.children()
    for node in module.nodes():
        for child in children[node]:
            parents[child] = node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            all_defs.append(node)
        elif isinstance(node, ast.Call):
            calls.append(node)

    defs: Dict[Tuple[int, str], ast.AST] = {}
    for node in all_defs:
        if any(_decorator_is_jit(d) for d in node.decorator_list):
            out.append(FunctionInfo(node, node.name, parents.get(node),
                                    traced_via="decorator"))
        else:
            defs[(id(parents.get(node)), node.name)] = node

    traced_ids = {id(fi.node) for fi in out}
    for node in calls:
        chain = call_chain(node)
        if chain is None or chain[-1] not in _TRACE_ENTRY_TAILS:
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            target: Optional[ast.AST] = None
            if isinstance(arg, ast.Lambda):
                target = arg
            elif isinstance(arg, ast.Name):
                # resolve to a def in any enclosing scope of the call site
                scope: Optional[ast.AST] = node
                while scope is not None and target is None:
                    target = defs.get((id(scope), arg.id))
                    scope = parents.get(scope)
            if target is not None and id(target) not in traced_ids:
                traced_ids.add(id(target))
                name = getattr(target, "name", "<lambda>")
                out.append(FunctionInfo(target, name, parents.get(target),
                                        traced_via=f"passed to {'.'.join(chain)}"))
    module._traced_functions = out
    return list(out)
