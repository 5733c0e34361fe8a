"""SWALLOWED-API — broad excepts that silently eat errors and fall through.

The PR 5 postmortem: ring/ulysses attention wrapped ``jax.lax.axis_size``
in ``except Exception`` with an ``n = 1`` fall-through; when a jax bump
removed the attribute, every rank silently attended only its local shard
("100% elements wrong" — no crash, no log, no test failure until a
stress matrix diffed numerics). The hazard is the *shape*, not the one
API: a broad/bare except whose handler neither re-raises, nor logs, nor
even looks at the exception, sitting over real work and falling through
to a default.

Fires on a broad handler (bare / ``Exception`` / ``BaseException``,
alone or in a tuple) when the handler body

  * contains no ``raise``,
  * makes no logging-ish call (``warnings.warn``, ``logging``/logger
    methods, ``print``, ``_log``), and
  * never reads the bound exception name (recording ``e`` somewhere is
    surfacing it),

and the try body contains at least one call. When the try body contains
a jax-derived call (alias-tracked: ``import jax.profiler as jp`` counts)
the message names the PR 5 class explicitly.

Suppress with ``# noqa: BLE001 — <reason>`` (the repo's existing
discipline) or ``# noqa: SWALLOWED-API — <reason>`` on the except line.
"""
import ast
from typing import Iterator, List, Tuple

from ..core import Finding, ParsedModule, Rule, is_jax_call, walk_stmts

_BROAD = {"Exception", "BaseException"}
_LOG_CALL_TAILS = {
    "warn", "warning", "error", "exception", "critical", "info", "debug",
    "log", "print",
}
_LOG_ROOTS = {"print", "_log", "log", "logger", "logging", "warnings"}


def _is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True
    if isinstance(t, ast.Name):
        return t.id in _BROAD
    if isinstance(t, ast.Tuple):
        return any(isinstance(el, ast.Name) and el.id in _BROAD
                   for el in t.elts)
    return False


def _is_logging_call(call: ast.Call) -> bool:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id in _LOG_ROOTS
    if isinstance(f, ast.Attribute):
        if f.attr in _LOG_CALL_TAILS:
            return True
        root = f.value
        while isinstance(root, ast.Attribute):
            root = root.value
        return isinstance(root, ast.Name) and root.id in _LOG_ROOTS
    return False


def _handler_is_silent(module: ParsedModule,
                       handler: ast.ExceptHandler) -> bool:
    bound = handler.name
    for node in walk_stmts(module, handler.body):
        if isinstance(node, ast.Raise):
            return False
        if isinstance(node, ast.Call) and _is_logging_call(node):
            return False
        if bound and isinstance(node, ast.Name) \
                and node.id == bound and isinstance(node.ctx, ast.Load):
            return False  # the exception is recorded/used somewhere
    return True


class SwallowedApiRule(Rule):
    name = "SWALLOWED-API"
    aliases = ("BLE001",)
    description = ("broad except that silently swallows errors from the "
                   "try body and falls through to a default (the PR 5 "
                   "silent-wrong-result class when jax APIs are involved)")

    def check(self, module: ParsedModule) -> Iterator[Finding]:
        hits: List[Tuple[int, str]] = []
        aliases = module.jax_aliases
        for node in module.nodes():
            if not isinstance(node, ast.Try):
                continue
            body_calls = [n for n in walk_stmts(module, node.body)
                          if isinstance(n, ast.Call)]
            if not body_calls:
                continue
            jax_calls = [c for c in body_calls if is_jax_call(c, aliases)]
            for handler in node.handlers:
                if not _is_broad(handler) or not _handler_is_silent(
                        module, handler):
                    continue
                if jax_calls:
                    api = ".".join(
                        _chain_str(jax_calls[0]))
                    msg = (f"broad except silently swallows errors from "
                           f"jax API call `{api}` and falls through to a "
                           f"default — the PR 5 silent-wrong-result class; "
                           f"re-raise, log, or annotate "
                           f"`# noqa: BLE001 — <reason>`")
                else:
                    msg = (f"broad except silently swallows all errors "
                           f"from {len(body_calls)} call site(s) with no "
                           f"re-raise, log, or use of the exception; "
                           f"narrow it, log the fall-through, or annotate "
                           f"`# noqa: BLE001 — <reason>`")
                hits.append((handler.lineno, msg))
        yield from self.findings(module, hits)


def _chain_str(call: ast.Call) -> List[str]:
    from ..core import call_chain

    return call_chain(call) or ["<call>"]
