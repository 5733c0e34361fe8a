"""STALE-CAPTURE — identity guards via id() and jitted closures over self.

The PR 1 postmortem: the SOT guard compared ``id()`` of a captured object
against a stored integer; the object died, CPython reused the id, and the
guard judged a *different* object "unchanged" — stale bytecode ran with
fresh inputs. The fix (compare ``is`` against a held reference) only
works if nobody reintroduces the pattern, which is exactly what a linter
is for.

Three shapes fire:

  * ``id(x) == y`` / ``y != id(x)`` — an identity compared by value. An
    id is only meaningful while the object is alive AND you hold a
    reference; equality against a stored int guards nothing.
  * ``self.attr = id(x)`` — storing an identity for a later guard, the
    precursor of the same bug.
  * a jit-traced function (decorated or passed to ``jax.jit``/friends)
    whose body *reads* ``self.<attr>`` — the attribute's value is baked
    in at trace time; later mutation of ``self`` silently keeps serving
    the stale constant from the executable cache.

Identity *maps* (``d[id(p)]`` with the object kept alive elsewhere) are
deliberately not flagged — that idiom holds its references.

Suppress with ``# noqa: STALE-CAPTURE — <reason>``.
"""
import ast
from typing import Iterator, List, Tuple

from ..core import Finding, ParsedModule, Rule, traced_functions


def _is_id_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "id")


class StaleCaptureRule(Rule):
    name = "STALE-CAPTURE"
    description = ("id()-based identity guards and jit-traced closures "
                   "reading mutable self state (the PR 1 stale-guard "
                   "class)")

    def check(self, module: ParsedModule) -> Iterator[Finding]:
        hits: List[Tuple[int, str]] = []
        for node in module.nodes():
            if isinstance(node, ast.Compare):
                sides = [node.left] + list(node.comparators)
                if any(_is_id_call(s) for s in sides) and any(
                        isinstance(op, (ast.Eq, ast.NotEq)) for op in node.ops):
                    hits.append((node.lineno,
                                 "id() compared by value — ids are reused "
                                 "after the object dies (the PR 1 stale "
                                 "SOT guard); hold the object and compare "
                                 "with `is` instead"))
            elif isinstance(node, ast.Assign):
                if _is_id_call(node.value) and any(
                        isinstance(t, ast.Attribute) for t in node.targets):
                    hits.append((node.lineno,
                                 "storing id() on an attribute for a later "
                                 "identity guard — the id is meaningless "
                                 "once the object dies; store the object "
                                 "(or a weakref) instead"))

        for info in traced_functions(module):
            fn = info.node
            body = fn.body
            if isinstance(body, list):
                body_nodes = [n for stmt in body for n in module.walk(stmt)]
            else:  # Lambda: .body is a single expression, not a list
                body_nodes = module.walk(body)
            for n in body_nodes:
                if (isinstance(n, ast.Attribute)
                        and isinstance(n.value, ast.Name)
                        and n.value.id == "self"
                        and isinstance(n.ctx, ast.Load)):
                    via = (f"@{info.traced_via}" if info.traced_via ==
                           "decorator" else info.traced_via)
                    hits.append((n.lineno,
                                 f"traced function `{info.name}` ({via}) "
                                 f"reads `self.{n.attr}` — captured at "
                                 f"trace time, so later mutation of self "
                                 f"silently serves a stale executable; "
                                 f"pass it as an argument (donated/static) "
                                 f"or snapshot it into a local before "
                                 f"tracing"))
                    break  # one finding per traced function is enough
        yield from self.findings(module, hits)
