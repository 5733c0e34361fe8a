"""COLLECTIVE-MESH — collectives must name a real mesh axis, every
``check_vma=False`` must say why, and ``ppermute`` rings must be sized
from the mesh.

Three contracts from the tensor-parallel work (PR 9 + ISSUE 18), all
about ``shard_map``:

  1. **Axis names.** ``jax.lax.psum(y, TP_AXIS)`` inside a
     shard_map-wrapped function runs on the axis the *wrap site's* mesh
     declares. A typo'd or stale axis name is the PR 5 swallowed-axis
     class all over again — it surfaces as a wrong *value*, not an
     error, once ``check_vma`` is off. The EQuARX/T3 roadmap items will
     multiply these sites, so the rule checks every collective whose
     axis operand *resolves to a string constant* (module-level
     constants like ``TP_AXIS = "tp"`` resolve, through from-imports
     too, via the project call graph's constant chase) against the
     union of axes declared by the module's resolvable ``Mesh(...)``
     constructors. Axis names that come in as function parameters
     (spmd_pipeline, moe) resolve to nothing and are skipped —
     conservative silence, not a guess.
  2. **check_vma=False.** Disabling varying-axes checking is sometimes
     required (PR 9's wrappers return per-shard outputs) but never
     free: every ``check_vma=False`` must carry
     ``# noqa: COLLECTIVE-MESH — <reason>`` *with a reason* on its
     line. A reasonless noqa is itself the finding — the rule inspects
     the noqa's reason tail directly and bypasses the normal
     suppression path for this sub-check, so you cannot silence the
     demand for a reason with the bare marker it is demanding.
  3. **Split-collective rings (ISSUE 18).** The overlap work moves
     psum payloads over fixed-order ``lax.ppermute`` rings. A
     permutation table written as a *literal* — ``[(0, 1), (1, 0)]``,
     or a comprehension over ``range(2)`` — encodes ONE tp degree: at
     any other degree it silently drops shards (values wrong, no
     error, same class as a stale axis name). Tables must be built
     from the declared mesh axis size (``parallel.mesh.ring_perm``);
     a table that arrives as a variable or helper call resolves to
     nothing and is trusted — same conservative silence as the axis
     check.

Scoped to modules that call shard_map at all; modules with no
resolvable mesh axes get only the check_vma audit and the ppermute
ring check (the literal-table hazard needs no mesh resolution).
"""
import ast
from typing import Iterator, List, Optional, Set, Tuple

from ..core import Finding, ParsedModule, Rule, dotted_chain

_COLLECTIVES = {"psum", "pmean", "pmax", "pmin", "all_gather",
                "ppermute", "pshuffle", "psum_scatter", "all_to_all"}
_MESH_TAILS = {"Mesh", "make_mesh"}


def _axis_operands(call: ast.Call) -> List[ast.expr]:
    """The expressions that may carry the axis name for a collective."""
    out = [kw.value for kw in call.keywords if kw.arg == "axis_name"]
    if not out and len(call.args) >= 2:
        out = [call.args[1]]
    return out


def _perm_operand(call: ast.Call) -> Optional[ast.expr]:
    """The expression carrying ppermute's permutation table, if present."""
    for kw in call.keywords:
        if kw.arg == "perm":
            return kw.value
    if len(call.args) >= 3:
        return call.args[2]
    return None


def _is_literal_perm(node: ast.expr) -> bool:
    """True when a perm table is hard-coded for one ring size.

    Fires on literal lists/tuples of pairs (``[(0, 1), (1, 0)]``) and on
    comprehensions whose only iterable is ``range(<constant>)`` — both
    pin the shard count at write time. Names and helper calls
    (``ring_perm(axis_size)``) are trusted: conservative silence.
    """
    if isinstance(node, (ast.List, ast.Tuple)):
        try:
            ast.literal_eval(node)
        except (ValueError, TypeError, SyntaxError, MemoryError):
            return False
        return True
    if isinstance(node, (ast.ListComp, ast.GeneratorExp)):
        if len(node.generators) != 1:
            return False
        it = node.generators[0].iter
        return (isinstance(it, ast.Call)
                and isinstance(it.func, ast.Name)
                and it.func.id == "range"
                and bool(it.args)
                and all(isinstance(a, ast.Constant) for a in it.args))
    return False


class CollectiveMeshRule(Rule):
    name = "COLLECTIVE-MESH"
    description = ("shard_map collectives whose axis name is not "
                   "declared by the module's mesh, and check_vma=False "
                   "without a reasoned noqa")

    def check(self, module: ParsedModule) -> Iterator[Finding]:
        from ..callgraph import Project
        return self.project_check(module, Project.single(module))

    def _resolve_axes(self, node: ast.expr, module: ParsedModule,
                      project) -> Tuple[Set[str], bool]:
        """(axis names, fully_resolved) for one axis-names expression."""
        if isinstance(node, ast.Constant):
            if isinstance(node.value, str):
                return {node.value}, True
            return set(), False
        if isinstance(node, (ast.Tuple, ast.List)):
            axes: Set[str] = set()
            complete = True
            for elt in node.elts:
                sub, ok = self._resolve_axes(elt, module, project)
                axes |= sub
                complete = complete and ok
            return axes, complete
        if isinstance(node, ast.Name):
            val = project.callgraph.resolve_constant(module.path, node.id)
            if isinstance(val, str):
                return {val}, True
            if isinstance(val, (tuple, list)) \
                    and all(isinstance(v, str) for v in val):
                return set(val), True
        return set(), False

    def _mesh_axes(self, module: ParsedModule,
                   project) -> Optional[Set[str]]:
        """Union of axis names of every resolvable Mesh constructor in
        the module; None when nothing resolves (skip axis checks)."""
        axes: Set[str] = set()
        found = False
        for node in module.nodes():
            if not isinstance(node, ast.Call):
                continue
            chain = dotted_chain(node.func)
            if chain is None or chain[-1] not in _MESH_TAILS:
                continue
            operand = None
            for kw in node.keywords:
                if kw.arg == "axis_names":
                    operand = kw.value
            if operand is None and len(node.args) >= 2:
                operand = node.args[1]
            if operand is None:
                continue
            sub, ok = self._resolve_axes(operand, module, project)
            if ok and sub:
                axes |= sub
                found = True
        return axes if found else None

    def _is_shard_map(self, chain: Optional[List[str]], module,
                      project) -> bool:
        if not chain:
            return False
        if chain[-1] == "shard_map":
            return True
        if len(chain) == 1:
            # `from ... import shard_map as _shard_map`: chase the alias
            binding = project.callgraph.imports_of(module.path) \
                .get(chain[0])
            return (binding is not None and binding[0] == "sym"
                    and binding[2] == "shard_map")
        return False

    def project_check(self, module: ParsedModule,
                      project) -> Iterator[Finding]:
        # call sites and `shard_map as _alias` imports both carry the
        # literal text; modules without it cannot have a shard site
        if "shard_map" not in module.source:
            return
        shard_sites = [
            node for node in module.nodes()
            if isinstance(node, ast.Call)
            and self._is_shard_map(dotted_chain(node.func), module,
                                   project)]
        if not shard_sites:
            return

        hits: List[Tuple[int, str]] = []
        mesh_axes = self._mesh_axes(module, project)
        for node in module.nodes():
            if not isinstance(node, ast.Call):
                continue
            chain = dotted_chain(node.func)
            if chain is None or chain[-1] not in _COLLECTIVES:
                continue
            if chain[0] not in module.jax_aliases \
                    and chain[0] != "lax":
                continue
            if mesh_axes is not None:
                for operand in _axis_operands(node):
                    axes, ok = self._resolve_axes(operand, module,
                                                  project)
                    if not ok:
                        continue  # parameter-carried axis: skip
                    for axis in sorted(axes - mesh_axes):
                        hits.append((node.lineno, (
                            f"collective `{'.'.join(chain)}` names axis "
                            f"'{axis}' but this module's shard_map "
                            f"meshes declare "
                            f"{sorted(mesh_axes)} — a stale axis name "
                            f"is the PR 5 swallowed-axis class: wrong "
                            f"values, no error, once check_vma is off")))
            if chain[-1] == "ppermute":
                perm = _perm_operand(node)
                if perm is not None and _is_literal_perm(perm):
                    hits.append((node.lineno, (
                        f"`{'.'.join(chain)}` builds its permutation "
                        f"table from a literal — a ring written for one "
                        f"tp degree silently drops shards at any other "
                        f"(wrong values, no error, the stale-axis class "
                        f"again); build it from the declared mesh axis "
                        f"size: `parallel.mesh.ring_perm(axis_size)`")))
        yield from self.findings(module, hits)

        # check_vma=False audit: bypasses inline suppression — a
        # reasonless `# noqa: COLLECTIVE-MESH` is exactly the bug
        occ: dict = {}
        for site in sorted(shard_sites, key=lambda n: (n.lineno,
                                                       n.col_offset)):
            for kw in site.keywords:
                if kw.arg != "check_vma":
                    continue
                if not (isinstance(kw.value, ast.Constant)
                        and kw.value.value is False):
                    continue
                line = kw.value.lineno
                reason = module.noqa_reason(line)
                if reason:
                    continue  # reasoned suppression: the contract held
                what = ("carries a reasonless `# noqa`" if reason == ""
                        else "has no `# noqa`")
                message = (
                    f"shard_map(check_vma=False) {what} — disabling "
                    f"replication checking hides axis mistakes (the "
                    f"PR 9 contract); justify it in place: "
                    f"`# noqa: COLLECTIVE-MESH — <why per-shard "
                    f"outputs are intended>`")
                snippet = module.line_text(line)
                k = (snippet, message)
                occ[k] = occ.get(k, -1) + 1
                yield Finding(rule=self.name, path=module.path,
                              line=line, message=message,
                              snippet=snippet, occurrence=occ[k])
