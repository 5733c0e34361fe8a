"""DONATED-REUSE — reading a buffer after passing it at a donated slot.

The engine's dispatch paths all follow one idiom (PR 2 onward): the
builder caches ``jax.jit(fn, donate_argnums=(3,))``, the call site
passes ``self.cache.pools`` at position 3, and the *very next
statement* rebinds it from the jit output::

    out = self._decode_block_jit(h)(params, buffers, tokens,
                                    self.cache.pools, ...)
    self.cache.pools = out[1]

After the dispatch the donated buffer is dead — XLA may have aliased
its pages into the output. Reading it again (or writing into it) before
the rebind returns garbage that no test catches deterministically: the
engine has 5+ donation sites and every one is a chance to ship the bug.

Detection is the v2 dataflow walk, one function frame at a time
(nested ``dispatch()`` closures are frames of their own):

  * a *donating callable* is either a direct ``jax.jit(...,
    donate_argnums=...)`` value or a call to a **builder** — any
    function whose own body contains such a ``jax.jit`` call (the
    ``_prefill_jit`` caching idiom). Builders resolve through the
    project call graph, so cross-module helpers count.
  * calling a donating callable marks the Name/attribute chain passed
    at each donated position (``self.cache.pools``) as donated;
  * any later load of that chain — or of an extension of it, or a
    store *into* it (``pools[i] = x``) — before a store that rebinds
    the chain (or a prefix) fires;
  * branches merge by union: donated on either path means donated.

Keyword-passed donated args and non-chain expressions are out of scope
(positional donation is the only idiom this repo uses).
"""
import ast
from typing import Dict, FrozenSet, Iterator, List, Optional, Set, Tuple

from ..core import Finding, ParsedModule, Rule, dotted_chain
from ..dataflow import EMPTY, FunctionDataflow, function_defs

_DONATED = "#donated"  # env key: frozenset of (chain, donated_at_line)


def _jit_donate_positions(call: ast.Call,
                          aliases: Set[str]) -> Optional[FrozenSet[int]]:
    """``jax.jit(f, donate_argnums=(3,))`` -> {3}; None when the call is
    not a donating jit."""
    chain = dotted_chain(call.func)
    if chain is None or chain[-1] != "jit" or chain[0] not in aliases:
        return None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            try:
                val = ast.literal_eval(kw.value)
            except (ValueError, SyntaxError):
                return None
            if isinstance(val, int):
                return frozenset({val})
            if isinstance(val, (tuple, list)) \
                    and all(isinstance(v, int) for v in val):
                return frozenset(val)
            return None
    return None


def _builder_positions(module: ParsedModule) -> Dict[int, FrozenSet[int]]:
    """id(def node) -> donated positions, for every function whose own
    body creates a donating jit (the ``_prefill_jit`` builder shape).
    One O(module) walk: each call attributes to its innermost def."""
    out: Dict[int, FrozenSet[int]] = {}
    children = module.children()

    def visit(node: ast.AST, owner: Optional[int]) -> None:
        for child in children[node]:
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                visit(child, id(child))
                continue
            if owner is not None and isinstance(child, ast.Call):
                pos = _jit_donate_positions(child, module.jax_aliases)
                if pos:
                    out[owner] = out.get(owner, frozenset()) | pos
            visit(child, owner)

    visit(module.tree, None)
    return out


class _Donating:
    """Abstract value: 'calling this donates these positions'."""

    __slots__ = ("positions",)

    def __init__(self, positions: FrozenSet[int]):
        self.positions = positions

    def __hash__(self):
        return hash(("donating", self.positions))

    def __eq__(self, other):
        return (isinstance(other, _Donating)
                and other.positions == self.positions)


class _Flow(FunctionDataflow):
    def __init__(self, module, project, builder_cache):
        super().__init__(module, project)
        self._builder_cache = builder_cache  # cross-module builder memo
        self.hits: List[Tuple[int, str]] = []
        self._fired: Set[Tuple[int, str]] = set()

    # -- builder resolution -------------------------------------------------
    def _positions_for_chain(self, chain) -> Optional[FrozenSet[int]]:
        # a builder's body textually contains donate_argnums, so the
        # project-wide name set is complete — any other tail name can
        # never resolve to one; skip the (indexing) call-graph walk
        if chain[-1] not in _builder_names(self.project,
                                           self._builder_cache):
            return None
        memo_key = ("chain", self.module.path, tuple(chain))
        if memo_key in self._builder_cache:
            return self._builder_cache[memo_key]
        graph = self.project.callgraph
        result = None
        for target in graph.resolve_chain(self.module.path, list(chain)):
            mod = self.project.module(target.key.path)
            if mod is None:
                continue
            pos = _builders_of(mod, self._builder_cache).get(
                id(target.node))
            if pos:
                result = pos
                break
        self._builder_cache[memo_key] = result
        return result

    # -- transfers ----------------------------------------------------------
    def call_result(self, call, chain, func_value, arg_values,
                    kw_values, env):
        donating: Set[_Donating] = {
            t for t in func_value if isinstance(t, _Donating)}
        if chain is not None:
            direct = _jit_donate_positions(call, self.module.jax_aliases)
            if direct:
                return frozenset({_Donating(direct)})
            pos = self._positions_for_chain(chain)
            if pos:
                return frozenset({_Donating(pos)})
        if donating:
            marked = env.get(_DONATED, EMPTY)
            for d in donating:
                for p in sorted(d.positions):
                    if p < len(call.args):
                        achain = dotted_chain(call.args[p])
                        if achain is not None:
                            marked = marked | {(".".join(achain),
                                               call.lineno)}
            env[_DONATED] = marked
        return None

    def _fire(self, chain: str, donated: str, line: int,
              donated_at: int, wrote: bool) -> None:
        key = (line, chain)
        if key in self._fired:
            return
        self._fired.add(key)
        verb = "written into" if wrote else "read"
        self.hits.append((line, (
            f"`{chain}` is {verb} after being passed at a donated "
            f"position of a jitted callable on line {donated_at} "
            f"(donate_argnums) — the buffer may already be aliased "
            f"into the jit output; rebind it from the output first "
            f"(`{donated} = out[...]`, the engine dispatch idiom) or "
            f"annotate `# noqa: DONATED-REUSE — <reason>`")))

    def on_load(self, chain, node, env):
        for donated, at in env.get(_DONATED, EMPTY):
            if chain == donated or chain.startswith(donated + "."):
                self._fire(chain, donated, getattr(node, "lineno", at),
                           at, wrote=False)

    def on_subscript_store(self, chain, node, env):
        for donated, at in env.get(_DONATED, EMPTY):
            if chain == donated or chain.startswith(donated + "."):
                self._fire(chain, donated, getattr(node, "lineno", at),
                           at, wrote=True)

    def on_store(self, chain, node, env):
        marked = env.get(_DONATED, EMPTY)
        if not marked:
            return
        keep = set()
        for donated, at in marked:
            if donated == chain or donated.startswith(chain + "."):
                continue  # rebound (or its base was): tracking ends
            if chain.startswith(donated + "."):
                # writing to an attribute OF the donated value is a use
                self._fire(chain, donated, getattr(node, "lineno", at),
                           at, wrote=True)
                continue
            keep.add((donated, at))
        env[_DONATED] = frozenset(keep)


def _builders_of(module: ParsedModule,
                 cache: Dict) -> Dict[int, FrozenSet[int]]:
    marker = ("module-builders", module.path)
    if marker not in cache:
        cache[marker] = _builder_positions(module)
    return cache[marker]


def _builder_names(project, cache: Dict) -> FrozenSet[str]:
    """Names of every donating-builder def in the project — the gate's
    cross-module half. Only modules whose text contains
    ``donate_argnums`` can define one, so the scan is cheap."""
    if "builder-names" not in cache:
        names = set()
        for mod in project.modules.values():
            if "donate_argnums" not in mod.source:
                continue
            table = _builders_of(mod, cache)
            if not table:
                continue
            for fn in function_defs(mod):
                if id(fn) in table:
                    names.add(fn.name)
        cache["builder-names"] = frozenset(names)
    return cache["builder-names"]


class DonatedReuseRule(Rule):
    name = "DONATED-REUSE"
    description = ("value passed at a jax.jit donate_argnums position "
                   "and read (or written into) again before being "
                   "rebound from the jit output")

    def check(self, module: ParsedModule) -> Iterator[Finding]:
        from ..callgraph import Project
        return self.project_check(module, Project.single(module))

    def project_check(self, module: ParsedModule,
                      project) -> Iterator[Finding]:
        # per-sweep memo: builder tables and chain resolutions survive
        # across modules within one Project
        builder_cache: Dict = project.scratch.setdefault(
            "donated-reuse", {})
        # gate: a module can only mark a donation if it creates a
        # donating jit itself or calls a builder by name (the name
        # appears textually even through import aliasing)
        if "donate_argnums" not in module.source:
            names = _builder_names(project, builder_cache)
            if not any(n in module.source for n in names):
                return
        frames = [module.tree] + list(function_defs(module))
        hits: List[Tuple[int, str]] = []
        for frame in frames:
            flow = _Flow(module, project, builder_cache)
            flow.run(frame)
            hits.extend(flow.hits)
        hits.sort()
        yield from self.findings(module, hits)
