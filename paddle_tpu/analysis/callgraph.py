"""Project call graph — import-resolving, built once per sweep.

PR 10's HOST-SYNC rule carried a private, same-module AST call graph
(`name -> def nodes`, bare/`self.`/`cls.` call edges, BFS from hot
roots). v2 generalizes that into a project-wide structure every rule
can query:

  * every parsed module contributes its function/method defs (nested
    defs included, exactly as the v1 table did);
  * per-module import tables resolve ``import x.y as z`` /
    ``from .mod import name`` (relative levels included) so call edges
    cross module boundaries when the callee is in the analyzed set;
  * ``self.f()`` / ``cls.f()`` resolve *by name within the module* —
    the v1 contract, kept deliberately so the HOST-SYNC port is
    behavior-identical (the serving modules have no colliding hot
    names, and over-approximating dispatch is the right failure mode
    for a linter);
  * ``reachable_names`` reproduces the v1 same-module BFS verbatim —
    it is the HOST-SYNC hot-set query.

Everything is syntactic: import *cycles* between analyzed modules are
just edges in both directions (nothing executes), and resolution
helpers that chase re-exports/constants are bounded-depth.

Pure stdlib; never imports jax (the tools/graftlint.py loader contract).
"""
import ast
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, Iterator, List, Mapping, Optional, \
    Sequence, Set, Tuple

from .core import ParsedModule, dotted_chain

_MAX_CHASE = 4  # re-export / constant chase bound (import cycles terminate)


@dataclass(frozen=True)
class FuncKey:
    """Stable identity of one def: (module path, dotted qualname, line)."""

    path: str
    qualname: str
    lineno: int


@dataclass(eq=False)  # identity hash: usable as a Summarizer memo key
class FuncNode:
    key: FuncKey
    name: str                 # bare name ("step")
    node: ast.AST             # FunctionDef | AsyncFunctionDef
    class_name: str = ""      # innermost enclosing class, "" for free fns


# one import binding: ("mod", dotted_module) or ("sym", dotted_module, name)
_Binding = Tuple


def module_dotted(path: str) -> Optional[str]:
    """'paddle_tpu/serving/engine.py' -> 'paddle_tpu.serving.engine';
    packages map to themselves; non-.py paths (fixtures) -> None."""
    if not path.endswith(".py"):
        return None
    parts = path[:-3].replace("\\", "/").split("/")
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts) if parts else None


def _package_of(path: str) -> Optional[str]:
    """The package a module's relative imports resolve against."""
    dotted = module_dotted(path)
    if dotted is None:
        return None
    if path.replace("\\", "/").endswith("/__init__.py"):
        return dotted
    return dotted.rsplit(".", 1)[0] if "." in dotted else ""


class CallGraph:
    """Defs, import tables and call edges over a set of parsed modules."""

    def __init__(self, modules: Mapping[str, ParsedModule]):
        self.modules: Dict[str, ParsedModule] = dict(modules)
        # dotted module name -> path, for every analyzed module
        self._path_of: Dict[str, str] = {}
        for path in self.modules:
            dotted = module_dotted(path)
            if dotted:
                self._path_of[dotted] = path
        self._funcs: Dict[FuncKey, FuncNode] = {}
        self._by_name: Dict[str, Dict[str, List[FuncNode]]] = {}
        self._imports: Dict[str, Dict[str, _Binding]] = {}
        self._called: Dict[FuncKey, FrozenSet[str]] = {}
        # call edges resolve lazily per function: a full sweep only pays
        # for the functions some rule actually asks about
        self._edges: Dict[FuncKey, FrozenSet[FuncKey]] = {}
        # module def/import tables also build lazily: the path map above
        # is pure string work, so a sweep where only a few modules get
        # queried (HOST-SYNC's hot set, DONATED-REUSE's gated modules)
        # never walks the other 170+ trees
        self._indexed: Set[str] = set()

    def _ensure(self, path: str) -> None:
        if path in self._indexed:
            return
        self._indexed.add(path)
        mod = self.modules.get(path)
        if mod is not None:
            self._index_module(path, mod)

    # -- indexing ----------------------------------------------------------
    def _index_module(self, path: str, mod: ParsedModule) -> None:
        table: Dict[str, List[FuncNode]] = {}
        self._by_name[path] = table

        children = mod.children()

        def visit(node: ast.AST, qual: str, cls: str) -> None:
            for child in children[node]:
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    q = f"{qual}.{child.name}" if qual else child.name
                    fn = FuncNode(FuncKey(path, q, child.lineno),
                                  child.name, child, cls)
                    self._funcs[fn.key] = fn
                    table.setdefault(child.name, []).append(fn)
                    visit(child, q, cls)
                elif isinstance(child, ast.ClassDef):
                    q = f"{qual}.{child.name}" if qual else child.name
                    visit(child, q, child.name)
                else:
                    visit(child, qual, cls)

        visit(mod.tree, "", "")
        self._imports[path] = _import_table(mod.nodes(), path)

    # -- module / symbol resolution ----------------------------------------
    def path_for_module(self, dotted: str) -> Optional[str]:
        return self._path_of.get(dotted)

    def imports_of(self, path: str) -> Mapping[str, _Binding]:
        self._ensure(path)
        return self._imports.get(path, {})

    def by_name(self, path: str) -> Mapping[str, List[FuncNode]]:
        self._ensure(path)
        return self._by_name.get(path, {})

    def functions_in(self, path: str) -> Iterator[FuncNode]:
        self._ensure(path)
        for nodes in self._by_name.get(path, {}).values():
            yield from nodes

    def function(self, key: FuncKey) -> Optional[FuncNode]:
        self._ensure(key.path)
        return self._funcs.get(key)

    def callees(self, key: FuncKey,
                same_module_only: bool = False) -> FrozenSet[FuncKey]:
        self._ensure(key.path)
        edges = self._edges.get(key)
        if edges is None:
            edges = frozenset(self._resolve_edges(key)) \
                if key in self._funcs else frozenset()
            self._edges[key] = edges
        if same_module_only:
            edges = frozenset(k for k in edges if k.path == key.path)
        return edges

    def _module_level_defs(self, path: str, name: str) -> List[FuncNode]:
        self._ensure(path)
        return [fn for fn in self._by_name.get(path, {}).get(name, [])
                if "." not in fn.key.qualname]

    def resolve_symbol(self, path: str, name: str,
                       _depth: int = 0) -> List[FuncNode]:
        """A bare name in `path` -> function defs it may denote: local
        defs first, then imported symbols (re-exports chased bounded)."""
        self._ensure(path)
        local = self._by_name.get(path, {}).get(name, [])
        if local:
            return list(local)
        if _depth >= _MAX_CHASE:
            return []
        binding = self._imports.get(path, {}).get(name)
        if binding is None:
            return []
        if binding[0] == "sym":
            target = self._path_of.get(binding[1])
            if target is None:
                return []
            defs = self._module_level_defs(target, binding[2])
            if defs:
                return defs
            return self.resolve_symbol(target, binding[2], _depth + 1)
        return []

    def resolve_chain(self, path: str,
                      chain: Sequence[str]) -> List[FuncNode]:
        """Resolve a dotted call chain to candidate defs.

        ``f`` -> local/imported function; ``self.f`` / ``cls.f`` -> any
        same-module def named f (the v1 by-name contract); ``mod.f`` /
        ``pkg.mod.f`` -> module-level f in the imported module.
        """
        if not chain:
            return []
        self._ensure(path)
        if len(chain) == 1:
            return self.resolve_symbol(path, chain[0])
        if chain[0] in {"self", "cls"} and len(chain) == 2:
            return list(self._by_name.get(path, {}).get(chain[1], []))
        # walk the chain as deep into the module namespace as it goes
        binding = self._imports.get(path, {}).get(chain[0])
        if binding is None:
            return []
        if binding[0] == "mod":
            dotted = binding[1]
        elif f"{binding[1]}.{binding[2]}" in self._path_of:
            dotted = f"{binding[1]}.{binding[2]}"  # `from . import mod`
        else:
            return []
        i = 1
        while i < len(chain) - 1 and f"{dotted}.{chain[i]}" in self._path_of:
            dotted = f"{dotted}.{chain[i]}"
            i += 1
        target = self._path_of.get(dotted)
        if target is None or i != len(chain) - 1:
            return []
        defs = self._module_level_defs(target, chain[-1])
        return defs or self.resolve_symbol(target, chain[-1], 1)

    def resolve_constant(self, path: str, name: str,
                         _depth: int = 0):
        """Module-level ``NAME = <literal>`` in `path`, chased through
        from-imports (bounded). Returns the literal value or None."""
        mod = self.modules.get(path)
        if mod is None or _depth >= _MAX_CHASE:
            return None
        self._ensure(path)
        for stmt in mod.tree.body:
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name) \
                    and stmt.targets[0].id == name:
                try:
                    return ast.literal_eval(stmt.value)
                except (ValueError, SyntaxError):
                    return None
        binding = self._imports.get(path, {}).get(name)
        if binding is not None and binding[0] == "sym":
            target = self._path_of.get(binding[1])
            if target is not None:
                return self.resolve_constant(target, binding[2], _depth + 1)
        return None

    # -- edges -------------------------------------------------------------
    def _resolve_edges(self, key: FuncKey) -> Set[FuncKey]:
        fn = self._funcs[key]
        out: Set[FuncKey] = set()
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            chain = dotted_chain(node.func)
            if chain is None:
                continue
            for callee in self.resolve_chain(key.path, chain):
                out.add(callee.key)
        return out

    # -- the HOST-SYNC hot-set query (v1 semantics, verbatim) --------------
    def reachable_names(self, path: str, roots: Set[str]) -> Set[str]:
        """Same-module, name-level BFS: exactly the PR 10 reachability
        contract (`self.f()`/`cls.f()`/`f()` edges, names not defs)."""
        self._ensure(path)
        table = self._by_name.get(path, {})
        seen: Set[str] = set()
        frontier = [r for r in roots if r in table]
        while frontier:
            name = frontier.pop()
            if name in seen:
                continue
            seen.add(name)
            for fn in table[name]:
                for callee in self._called_for(fn.key):
                    if callee in table and callee not in seen:
                        frontier.append(callee)
        return seen

    def _called_for(self, key: FuncKey) -> FrozenSet[str]:
        """Called-name set per def, computed on first BFS touch — an
        ast.walk per def is too expensive to pay at indexing time."""
        got = self._called.get(key)
        if got is None:
            fn = self._funcs.get(key)
            got = frozenset(_called_names(fn.node)) if fn else frozenset()
            self._called[key] = got
        return got


def _called_names(fn: ast.AST) -> Set[str]:
    """Names invoked as ``self.f(...)``, ``cls.f(...)`` or ``f(...)``
    anywhere inside fn (nested defs included — a closure's calls belong
    to the function that runs it; the v1 HOST-SYNC contract)."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name):
            out.add(f.id)
        elif (isinstance(f, ast.Attribute)
              and isinstance(f.value, ast.Name)
              and f.value.id in {"self", "cls"}):
            out.add(f.attr)
    return out


def _import_table(nodes, path: str) -> Dict[str, _Binding]:
    """name -> binding for every import anywhere in the module
    (function-local imports included — same policy as jax_aliases).
    `nodes` is any iterable of AST nodes (ParsedModule.nodes())."""
    table: Dict[str, _Binding] = {}
    package = _package_of(path)
    for node in nodes:
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    table[a.asname] = ("mod", a.name)
                else:
                    root = a.name.split(".")[0]
                    table.setdefault(root, ("mod", root))
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                if package is None:
                    continue  # fixture path: relative base unknowable
                parts = package.split(".") if package else []
                drop = node.level - 1
                if drop > len(parts):
                    continue
                kept = parts[:len(parts) - drop] if drop else parts
                base = ".".join(kept + ([node.module] if node.module else []))
            if not base:
                continue
            for a in node.names:
                if a.name == "*":
                    continue
                table[a.asname or a.name] = ("sym", base, a.name)
    return table


@dataclass
class Project:
    """Everything a project-aware rule may query: the full parsed-module
    set plus the call graph built once over it."""

    modules: Dict[str, ParsedModule] = field(default_factory=dict)
    _callgraph: Optional[CallGraph] = None
    # per-sweep scratch space for rule memos (builder tables, function
    # summaries): lives exactly as long as the Project, so cross-module
    # work is paid once per sweep instead of once per analyzed module
    scratch: Dict = field(default_factory=dict)

    @property
    def callgraph(self) -> CallGraph:
        if self._callgraph is None:
            self._callgraph = CallGraph(self.modules)
        return self._callgraph

    def module(self, path: str) -> Optional[ParsedModule]:
        return self.modules.get(path)

    @classmethod
    def single(cls, module: ParsedModule) -> "Project":
        return cls(modules={module.path: module})
