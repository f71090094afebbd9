"""``pallas-conventions`` — repo conventions for Pallas TPU kernels.

Every kernel in ``kernels/`` follows the same contract (established in
PR 2 and load-bearing ever since: the xla/pallas impl switch in
``ops.py`` is what lets CI validate kernels in interpret mode against
their oracles):

  1. **oracle** — each public kernel entry point ``foo`` in
     ``kernels/foo.py`` has a pure-jnp reference ``foo_ref`` in
     ``kernels/ref.py``;
  2. **dispatch** — ``kernels/ops.py`` imports the kernel, so the
     ``impl={"xla","pallas"}`` switch covers it;
  3. **index maps** — BlockSpec/GridSpec index-map lambdas must not close
     over mutable state (module globals that are reassigned, or locals
     bound to list/dict/set values): they are traced once and cached, so
     a mutated closure silently changes addressing;
  4. **aliasing** — ``input_output_aliases`` keys must be valid operand
     indices of the actual ``pl.pallas_call(...)(...)`` invocation
     (scalar-prefetch args included) and values valid ``out_shape``
     indices;
  5. **no Python branching on traced refs** — ``if``/``while`` on values
     read from ``*_ref`` parameters is a tracer error at best and a
     silent specialization at worst; use ``@pl.when`` / ``jnp.where``.
"""
from __future__ import annotations

import ast
import pathlib
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis.framework import (AnalysisPass, Finding, SourceFile,
                                      register)

_NON_KERNEL_FILES = {"__init__.py", "ops.py", "ref.py"}
_MUTABLE_CTORS = {"list", "dict", "set", "bytearray", "deque", "defaultdict"}


def _lambda_free_names(lam: ast.Lambda) -> Set[str]:
    bound = {a.arg for a in (lam.args.posonlyargs + lam.args.args
                             + lam.args.kwonlyargs)}
    if lam.args.vararg:
        bound.add(lam.args.vararg.arg)
    if lam.args.kwarg:
        bound.add(lam.args.kwarg.arg)
    free: Set[str] = set()
    for node in ast.walk(lam.body):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            free.add(node.id)
        elif isinstance(node, ast.Lambda):
            # nested lambda params shadow — rare enough to ignore here
            pass
    import builtins
    return {n for n in free - bound if not hasattr(builtins, n)}


@register
class PallasConventionsPass(AnalysisPass):
    name = "pallas-conventions"
    description = ("kernels declare a jnp oracle in ref.py + a dispatch in "
                   "ops.py; index maps don't close over mutable state; "
                   "input_output_aliases indices are valid; no Python "
                   "branching on traced refs")
    hint = ("see docs/static_analysis.md#pallas-conventions and the "
            "existing kernels for the contract")
    targets = ("src/repro/kernels",)
    kernels_dir = "src/repro/kernels"

    def run(self, repo: pathlib.Path,
            files: Sequence[SourceFile]) -> List[Finding]:
        out: List[Finding] = []
        kdir = (repo / self.kernels_dir).resolve()
        kernel_files = [sf for sf in files
                        if sf.path.parent.resolve() == kdir
                        and sf.tree is not None]
        ref_sf = next((sf for sf in kernel_files
                       if sf.path.name == "ref.py"), None)
        ops_sf = next((sf for sf in kernel_files
                       if sf.path.name == "ops.py"), None)
        ref_defs: Set[str] = set()
        if ref_sf is not None and ref_sf.tree is not None:
            ref_defs = {n.name for n in ref_sf.tree.body
                        if isinstance(n, ast.FunctionDef)}
        ops_imports: Set[str] = set()
        if ops_sf is not None and ops_sf.tree is not None:
            for n in ast.walk(ops_sf.tree):
                if isinstance(n, ast.ImportFrom) and n.module:
                    ops_imports.add(n.module)

        for sf in kernel_files:
            if sf.path.name in _NON_KERNEL_FILES:
                continue
            out.extend(self._check_kernel_module(sf, ref_defs, ops_imports))
        for sf in kernel_files:
            if sf.tree is None:
                continue
            out.extend(self._check_index_maps(sf))
            out.extend(self._check_aliases(sf))
            out.extend(self._check_traced_branching(sf))
        return out

    # ------------------------------------------------------------------
    # 1 + 2: oracle in ref.py, dispatch in ops.py
    def _check_kernel_module(self, sf: SourceFile, ref_defs: Set[str],
                             ops_imports: Set[str]) -> Iterable[Finding]:
        assert sf.tree is not None
        mod = sf.path.stem
        entries = [n for n in sf.tree.body if isinstance(n, ast.FunctionDef)
                   and not n.name.startswith("_")]
        if not entries:
            return
        expected_mod = f"repro.kernels.{mod}"
        if expected_mod not in ops_imports:
            yield self.finding(
                sf, 1,
                f"kernel module `{mod}` is not dispatched: ops.py never "
                f"imports `{expected_mod}`",
                hint="add an impl-switched wrapper in kernels/ops.py so the "
                     "xla/pallas toggle covers this kernel")
        for entry in entries:
            if f"{entry.name}_ref" not in ref_defs:
                yield self.finding(
                    sf, entry.lineno,
                    f"kernel entry `{entry.name}` has no jnp oracle "
                    f"`{entry.name}_ref` in kernels/ref.py",
                    hint="every Pallas kernel ships a pure-jnp reference in "
                         "kernels/ref.py — it is the CI correctness oracle")

    # ------------------------------------------------------------------
    # 3: index maps must not close over mutable state
    def _check_index_maps(self, sf: SourceFile) -> Iterable[Finding]:
        assert sf.tree is not None
        module_assigns: Dict[str, int] = {}
        global_names: Set[str] = set()
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Global):
                global_names.update(node.names)
        for node in sf.tree.body:
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        module_assigns[t.id] = \
                            module_assigns.get(t.id, 0) + 1
            elif isinstance(node, ast.AnnAssign) \
                    and isinstance(node.target, ast.Name):
                module_assigns[node.target.id] = \
                    module_assigns.get(node.target.id, 0) + 1

        for func in ast.walk(sf.tree):
            if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            params = {a.arg for a in (func.args.posonlyargs + func.args.args
                                      + func.args.kwonlyargs)}
            mutable_locals: Dict[str, int] = {}
            for stmt in ast.walk(func):
                if isinstance(stmt, ast.Assign):
                    val = stmt.value
                    is_mut = isinstance(val, (ast.List, ast.Dict, ast.Set,
                                              ast.ListComp, ast.DictComp,
                                              ast.SetComp)) or (
                        isinstance(val, ast.Call)
                        and isinstance(val.func, ast.Name)
                        and val.func.id in _MUTABLE_CTORS)
                    if is_mut:
                        for t in stmt.targets:
                            if isinstance(t, ast.Name):
                                mutable_locals[t.id] = stmt.lineno
            for call in ast.walk(func):
                if not (isinstance(call, ast.Call)
                        and isinstance(call.func, (ast.Attribute, ast.Name))):
                    continue
                fname = call.func.attr if isinstance(call.func, ast.Attribute)\
                    else call.func.id
                if fname != "BlockSpec":
                    continue
                lambdas = [a for a in list(call.args)
                           + [k.value for k in call.keywords]
                           if isinstance(a, ast.Lambda)]
                for lam in lambdas:
                    for name in sorted(_lambda_free_names(lam)):
                        if name in global_names or \
                                module_assigns.get(name, 0) > 1:
                            yield self.finding(
                                sf, lam.lineno,
                                f"index map closes over module-level "
                                f"mutable/reassigned name `{name}`",
                                hint="index maps are traced once — pass the "
                                     "value through scalar prefetch or bind "
                                     "it as a default arg")
                        elif name in mutable_locals:
                            yield self.finding(
                                sf, lam.lineno,
                                f"index map closes over `{name}`, a local "
                                f"bound to a mutable container "
                                f"(line {mutable_locals[name]})",
                                hint="index maps are traced once — close "
                                     "over immutable ints/shapes only")
                        elif name not in params \
                                and name not in module_assigns \
                                and not self._bound_in(func, name):
                            # unknown free name: imported module attr etc.
                            continue

    @staticmethod
    def _bound_in(func: ast.AST, name: str) -> bool:
        for stmt in ast.walk(func):
            if isinstance(stmt, ast.Assign):
                for t in stmt.targets:
                    if isinstance(t, ast.Name) and t.id == name:
                        return True
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                for t in ast.walk(stmt.target):
                    if isinstance(t, ast.Name) and t.id == name:
                        return True
        return False

    # ------------------------------------------------------------------
    # 4: input_output_aliases indices
    def _check_aliases(self, sf: SourceFile) -> Iterable[Finding]:
        assert sf.tree is not None
        for outer in ast.walk(sf.tree):
            # the invocation shape: pl.pallas_call(...)( *operands )
            if not (isinstance(outer, ast.Call)
                    and isinstance(outer.func, ast.Call)):
                continue
            inner = outer.func
            f = inner.func
            fname = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else None)
            if fname != "pallas_call":
                continue
            aliases = next((k.value for k in inner.keywords
                            if k.arg == "input_output_aliases"), None)
            if not isinstance(aliases, ast.Dict):
                continue
            if any(isinstance(a, ast.Starred) for a in outer.args) \
                    or outer.keywords:
                continue  # can't count operands statically
            n_operands = len(outer.args)
            out_shape = next((k.value for k in inner.keywords
                              if k.arg == "out_shape"), None)
            n_out: Optional[int] = None
            if isinstance(out_shape, (ast.List, ast.Tuple)):
                n_out = len(out_shape.elts)
            elif out_shape is not None and isinstance(out_shape, ast.Call):
                n_out = 1
            for k, v in zip(aliases.keys, aliases.values):
                if isinstance(k, ast.Constant) and isinstance(k.value, int) \
                        and not (0 <= k.value < n_operands):
                    yield self.finding(
                        sf, k.lineno,
                        f"input_output_aliases key {k.value} is out of "
                        f"range: the pallas_call invocation passes "
                        f"{n_operands} operand(s)",
                        hint="operand indices count scalar-prefetch args "
                             "first — recount against the actual call")
                if n_out is not None and isinstance(v, ast.Constant) \
                        and isinstance(v.value, int) \
                        and not (0 <= v.value < n_out):
                    yield self.finding(
                        sf, v.lineno,
                        f"input_output_aliases value {v.value} is out of "
                        f"range: out_shape declares {n_out} output(s)")

    # ------------------------------------------------------------------
    # 5: no Python branching on traced refs
    def _check_traced_branching(self, sf: SourceFile) -> Iterable[Finding]:
        assert sf.tree is not None

        def walk_own(root: ast.AST) -> Iterable[ast.AST]:
            """Nodes of this scope only — nested def subtrees excluded."""
            stack: List[ast.AST] = list(ast.iter_child_nodes(root))
            while stack:
                node = stack.pop()
                yield node
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef, ast.Lambda)):
                    stack.extend(ast.iter_child_nodes(node))

        def scan(func, inherited: Set[str]) -> Iterable[Finding]:
            params = {a.arg for a in (func.args.posonlyargs + func.args.args
                                      + func.args.kwonlyargs)}
            tainted = set(inherited) | {p for p in params
                                        if p.endswith("_ref")}
            nested = []
            if tainted:
                # two passes: collect taint via assignments first so a use
                # before its (lexically later) def in a loop still counts
                for _ in range(2):
                    for node in walk_own(func):
                        if isinstance(node, ast.Assign):
                            names = {n.id for n in ast.walk(node.value)
                                     if isinstance(n, ast.Name)}
                            if names & tainted:
                                for t in node.targets:
                                    if isinstance(t, ast.Name):
                                        tainted.add(t.id)
                for node in walk_own(func):
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        nested.append(node)
                    if isinstance(node, (ast.If, ast.While)):
                        test_names = {n.id for n in ast.walk(node.test)
                                      if isinstance(n, ast.Name)}
                        hit = sorted(test_names & tainted)
                        if hit:
                            kw = "while" if isinstance(node, ast.While) \
                                else "if"
                            yield self.finding(
                                sf, node.lineno,
                                f"Python `{kw}` branches on traced value(s) "
                                f"{', '.join(hit)} derived from a kernel "
                                f"ref",
                                hint="use @pl.when / jnp.where — Python "
                                     "control flow on traced values is a "
                                     "trace-time constant, not a runtime "
                                     "branch")
            else:
                for node in walk_own(func):
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        nested.append(node)
            for sub in nested:
                yield from scan(sub, tainted)

        for func in sf.tree.body:
            if isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from scan(func, set())
