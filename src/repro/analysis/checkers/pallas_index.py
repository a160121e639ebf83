"""``pallas-index`` — indexing and arity contracts inside Pallas kernels.

The seed's RG-LRU kernel shipped with a raw Python-int store index where
``pl.dslice`` was required (fixed in PR 2); this checker makes that class
of defect unrepresentable.  Kernel bodies are the functions handed to
``pl.pallas_call`` (directly, through ``functools.partial``, or via an
assigned alias); ref parameters are the kernel's positional arguments.

  PI1  ``pl.load(ref, (...idx...))``, and the same index tuple in the
       ``pl.store`` call of JAX before 0.9: every index element must be static — an int literal, ``slice``,
       ``Ellipsis``/``None`` — or an explicit ``pl.dslice``/``pl.ds``.
       A dynamic element (a loop variable, ``program_id`` arithmetic)
       indexes relative to the block mapping with *element* granularity
       only if wrapped in ``dslice``; raw, it silently misaddresses.
  PI2  subscript *stores* on ref parameters (``ref[i] = ...``) with a
       dynamic index element — same contract as PI1.  Dynamic *reads*
       of scalar-prefetch refs (``lens_ref[b]``) are legal and common.
  PI3  BlockSpec arity: index-map lambdas must take exactly
       ``len(grid) + num_scalar_prefetch`` arguments, and an index map
       returning a tuple literal must match its block-shape rank.
       Mismatches trace fine in interpret mode and fail (or misaddress)
       on hardware.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from repro.analysis.core import Checker, Finding, SourceModule

_STORE_LOAD = {
    "jax.experimental.pallas.store": "pl.store",
    "jax.experimental.pallas.load": "pl.load",
}
_DSLICE = {
    "jax.experimental.pallas.dslice",
    "jax.experimental.pallas.ds",
}
_BLOCKSPEC = "jax.experimental.pallas.BlockSpec"
_GRID_SPECS = {
    "jax.experimental.pallas.tpu.PrefetchScalarGridSpec",
}
_PALLAS_CALL = "jax.experimental.pallas.pallas_call"


class PallasIndexChecker(Checker):
    rule = "pallas-index"

    def check(self, mod: SourceModule) -> List[Finding]:
        out: List[Finding] = []
        for info in mod.functions.values():
            if info.kernel:
                self._check_kernel(mod, info.node, out)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                name = mod.dotted(node.func)
                if name == _PALLAS_CALL or name in _GRID_SPECS:
                    self._check_arity(mod, node, out)
        return out

    # -- PI1 / PI2: dynamic indices ----------------------------------------

    def _check_kernel(self, mod: SourceModule, fn: ast.AST,
                      out: List[Finding]) -> None:
        refs = self._ref_params(fn)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = mod.dotted(node.func)
                if name in _STORE_LOAD and len(node.args) >= 2:
                    self._check_index(mod, node.args[1], _STORE_LOAD[name],
                                      out)
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Subscript) \
                            and isinstance(t.value, ast.Name) \
                            and t.value.id in refs:
                        self._check_index(
                            mod, t.slice, f"store to {t.value.id}[...]",
                            out)
            elif isinstance(node, ast.AugAssign) \
                    and isinstance(node.target, ast.Subscript) \
                    and isinstance(node.target.value, ast.Name) \
                    and node.target.value.id in refs:
                self._check_index(
                    mod, node.target.slice,
                    f"store to {node.target.value.id}[...]", out)

    @staticmethod
    def _ref_params(fn: ast.AST) -> Set[str]:
        args = getattr(fn, "args", None)
        if args is None:
            return set()
        return {a.arg for a in list(args.posonlyargs) + list(args.args)}

    def _check_index(self, mod: SourceModule, idx: ast.AST, where: str,
                     out: List[Finding]) -> None:
        elems = list(idx.elts) if isinstance(idx, ast.Tuple) else [idx]
        for e in elems:
            if self._static_index(mod, e):
                continue
            out.append(self.finding(
                mod, e,
                f"raw dynamic index {ast.unparse(e)!r} in {where} — wrap "
                f"dynamic positions in pl.dslice (a raw value "
                f"misaddresses relative to the block mapping)"))

    def _static_index(self, mod: SourceModule, e: ast.AST) -> bool:
        if isinstance(e, ast.Constant):
            return e.value is None or e.value is Ellipsis \
                or isinstance(e.value, int)
        if isinstance(e, ast.Slice):
            return True
        if isinstance(e, ast.UnaryOp) and isinstance(e.operand, ast.Constant):
            return True
        if isinstance(e, ast.Call):
            name = mod.dotted(e.func)
            return name in _DSLICE or name == "slice"
        return False

    # -- PI3: BlockSpec / grid arity ---------------------------------------

    def _check_arity(self, mod: SourceModule, call: ast.Call,
                     out: List[Finding]) -> None:
        grid: Optional[int] = None
        prefetch = 0
        for kw in call.keywords:
            if kw.arg == "grid" and isinstance(kw.value, ast.Tuple):
                grid = len(kw.value.elts)
            elif kw.arg == "num_scalar_prefetch" \
                    and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, int):
                prefetch = kw.value.value
            elif kw.arg == "grid_spec":
                return  # arity checked on the inner grid-spec call
        if grid is None:
            return
        expected = grid + prefetch
        for spec in (n for kw in call.keywords
                     if kw.arg in ("in_specs", "out_specs")
                     for n in ast.walk(kw.value)):
            if not (isinstance(spec, ast.Call)
                    and mod.dotted(spec.func) == _BLOCKSPEC):
                continue
            shape = spec.args[0] if spec.args else None
            index_map = spec.args[1] if len(spec.args) > 1 else None
            for kw in spec.keywords:
                if kw.arg == "block_shape":
                    shape = kw.value
                elif kw.arg == "index_map":
                    index_map = kw.value
            if isinstance(index_map, ast.Lambda):
                n_args = len(index_map.args.args) \
                    + len(index_map.args.posonlyargs)
                if index_map.args.vararg is None and n_args != expected:
                    out.append(self.finding(
                        mod, index_map,
                        f"BlockSpec index map takes {n_args} args but the "
                        f"grid supplies {expected} "
                        f"({grid} grid dims + {prefetch} scalar-prefetch "
                        f"refs)"))
                if isinstance(shape, ast.Tuple) \
                        and isinstance(index_map.body, ast.Tuple) \
                        and len(index_map.body.elts) != len(shape.elts):
                    out.append(self.finding(
                        mod, index_map,
                        f"BlockSpec index map returns "
                        f"{len(index_map.body.elts)} coordinates for a "
                        f"rank-{len(shape.elts)} block shape"))
