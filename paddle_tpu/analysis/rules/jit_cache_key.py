"""JIT-CACHE-KEY — executable-cache keys missing a Python-level argument.

The engine builds jitted executables once and caches them in
``self._jit_cache[key]``; the key tuple must contain every Python-level
value the traced closure specializes on. Miss one and two different
configurations silently share one executable — the stale-executable
hazard the ``("tp", N, device_ids)`` key from PR 9 was designed around
(two meshes, one cached program: wrong collectives, no error).

Detection targets the repo's idiom exactly:

    def _prefill_jit(self, bucket):
        key = ("prefill", bucket) + (tp.jit_key if tp else ())
        if key not in self._jit_cache:
            ...
            self._jit_cache[key] = jax.jit(prefill, ...)
        return self._jit_cache[key]

A function fires when it (a) assigns a tuple-valued cache key, (b)
indexes a ``*cache*``-named container with it, (c) calls ``jax.jit``,
and (d) has a parameter (beyond self/cls) that never reaches the key
expression — directly or through local derivations (``b, prompt_len =
ids.shape`` covers ``ids``; a one-pass transitive closure over plain
assignments) — that parameter shapes the closure but not the cache
identity. A parameter that IS the key (``def _compiled_for(self, sig)``)
is covered by definition.

Suppress with ``# noqa: JIT-CACHE-KEY — <reason>`` on the key
assignment line (for parameters that genuinely don't reach the traced
program).
"""
import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from ..core import Finding, ParsedModule, Rule, dotted_chain

_JIT_CHAINS = {("jax", "jit"), ("jit",)}


def _contains_tuple(expr: ast.AST) -> bool:
    return any(isinstance(n, ast.Tuple) for n in ast.walk(expr))


def _is_jit_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    chain = dotted_chain(node.func)
    return chain is not None and tuple(chain) in _JIT_CHAINS


def _cache_subscript_keys(nodes: List[ast.AST]) -> Set[str]:
    """Names used to index a container whose attribute/name mentions
    'cache', e.g. `self._jit_cache[key]`."""
    keys: Set[str] = set()
    for node in nodes:
        if not isinstance(node, ast.Subscript):
            continue
        base = node.value
        base_name = ""
        if isinstance(base, ast.Attribute):
            base_name = base.attr
        elif isinstance(base, ast.Name):
            base_name = base.id
        if "cache" not in base_name.lower():
            continue
        idx = node.slice
        if isinstance(idx, ast.Name):
            keys.add(idx.id)
    return keys


class JitCacheKeyRule(Rule):
    name = "JIT-CACHE-KEY"
    description = ("jit executable-cache key tuples missing a Python-"
                   "level parameter of the builder — two configs would "
                   "share one stale executable (the PR 9 tp-key class)")

    def check(self, module: ParsedModule) -> Iterator[Finding]:
        hits: List[Tuple[int, str]] = []
        if not any(_is_jit_call(node) for node in module.nodes()):
            return      # most modules build no executable
        for fn in module.nodes():
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            fn_nodes = module.walk(fn)
            if not any(_is_jit_call(node) for node in fn_nodes):
                continue
            cache_keys = _cache_subscript_keys(fn_nodes)
            if not cache_keys:
                continue
            # the key assignment(s): `key = <expr with a tuple>`
            key_assigns: List[ast.Assign] = []
            for node in fn_nodes:
                if (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and node.targets[0].id in cache_keys
                        and _contains_tuple(node.value)):
                    key_assigns.append(node)
            if not key_assigns:
                continue
            params = [a.arg for a in (fn.args.posonlyargs + fn.args.args
                                      + fn.args.kwonlyargs)
                      if a.arg not in {"self", "cls"}]
            if fn.args.vararg is not None:
                params.append(fn.args.vararg.arg)
            if fn.args.kwarg is not None:
                params.append(fn.args.kwarg.arg)
            if not params:
                continue
            key_names: Set[str] = set()
            for ka in key_assigns:
                for n in ast.walk(ka.value):
                    if isinstance(n, ast.Name):
                        key_names.add(n.id)
            # one-pass derivation map: `b, prompt_len = ids.shape` means a
            # key containing `b` covers parameter `ids`
            derived: Dict[str, Set[str]] = {}
            for node in fn_nodes:
                if isinstance(node, ast.Assign):
                    srcs = {n.id for n in ast.walk(node.value)
                            if isinstance(n, ast.Name)}
                    for t in node.targets:
                        for n in ast.walk(t):
                            if isinstance(n, ast.Name):
                                derived.setdefault(n.id, set()).update(srcs)
            covered: Set[str] = set()
            frontier = list(key_names | cache_keys)  # the key IS coverage
            while frontier:
                name = frontier.pop()
                if name in covered:
                    continue
                covered.add(name)
                frontier.extend(derived.get(name, ()))
            missing = [p for p in params if p not in covered]
            for p in missing:
                hits.append((
                    key_assigns[0].lineno,
                    f"parameter `{p}` of `{fn.name}` does not appear in "
                    f"the jit cache key — two values of `{p}` would share "
                    f"one cached executable (the PR 9 stale-executable "
                    f"class); add it to the key tuple or annotate "
                    f"`# noqa: JIT-CACHE-KEY — <reason>`"))
        yield from self.findings(module, hits)
