"""pallas-vmem / pallas-dma: static resource checks on Pallas kernels.

**pallas-vmem** — a ``pallas_call``'s on-chip footprint is decidable from
its call site: BlockSpec block shapes (×2: the grid pipeline
double-buffers every blocked operand) plus ``scratch_shapes`` VMEM
allocations. The checker evaluates the shape expressions with a table of
worst-case dimension bounds (``AnalysisConfig.assumed_dims``, CLI
``--assume NAME=VALUE``) and flags kernels whose upper-bound estimate
exceeds the per-core VMEM cap (default 16 MiB). An over-budget kernel
compiles on the interpret path CI runs and only explodes on real TPUs —
exactly the failure a static bound catches early. SMEM blocks and
``memory_space=ANY`` operands (manual-DMA HBM residents) don't occupy
VMEM blocks and are excluded.

**pallas-dma** — every manually-issued DMA (``pltpu.make_async_copy(...)
.start()``) must have a matching ``.wait()`` on the *same semaphore
expression* somewhere in the module (start and wait legitimately live in
different helpers, e.g. a fill/drain pair). A started-but-never-awaited
copy races the buffer consumer; the interpret path hides it.

Both rules also understand the in-place row-update idiom
(``input_output_aliases`` + ``memory_space=ANY`` pools + a DMA-semaphore
array scratch):

* pallas-dma bounds-checks semaphore slots: when the kernel function is
  statically resolvable (a plain ``def``, possibly behind
  ``functools.partial``) and a ``scratch_shapes`` entry declares
  ``pltpu.SemaphoreType.DMA((k,))``, any constant ``sem.at[i]`` with
  ``i >= k`` in that kernel is flagged — an out-of-range slot aliases a
  neighbouring semaphore and deadlocks or silently corrupts on real TPUs
  while interpret mode shrugs.
* pallas-vmem validates ``input_output_aliases`` dict literals: operand
  indices must be in range of the literal ``in_specs``/``out_specs``
  lists, and an aliased input/output pair must live in the *same* memory
  space (aliasing names one buffer; a VMEM-blocked input aliased onto an
  ``ANY`` output — or vice versa — is a miscounted operand index until it
  explodes at lowering time).
"""
from __future__ import annotations

import ast
import math
from typing import Dict, Iterator, List, Optional, Set, Tuple

from repro.analysis.engine import Finding, ModuleContext, Rule
from repro.analysis.purity import _attr_chain

_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool_": 1, "bool": 1,
}


def _eval_dim(node: ast.AST, dims: Dict[str, int], default: int) -> int:
    """Upper-bound a block-shape dimension expression."""
    if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
        return int(node.value)
    if isinstance(node, ast.Name):
        return dims.get(node.id, default)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -_eval_dim(node.operand, dims, default)
    if isinstance(node, ast.BinOp):
        left = _eval_dim(node.left, dims, default)
        right = _eval_dim(node.right, dims, default)
        if isinstance(node.op, ast.Add):
            return left + right
        if isinstance(node.op, ast.Sub):
            return left - right
        if isinstance(node.op, ast.Mult):
            return left * right
        if isinstance(node.op, (ast.Div, ast.FloorDiv)):
            return left // max(right, 1)
        if isinstance(node.op, ast.Mod):
            return max(right - 1, 0)
        if isinstance(node.op, ast.Pow):
            return left ** right
    if isinstance(node, ast.Call):
        chain = _attr_chain(node.func)
        if chain and chain[-1] == "Squeezed":
            return 1  # a squeezed block dim holds one element
        vals = [_eval_dim(a, dims, default) for a in node.args]
        if chain and vals:
            if chain[-1] == "max":
                return max(vals)
            if chain[-1] == "min":
                return min(vals)
            if chain[-1] == "cdiv" and len(vals) == 2:
                return math.ceil(vals[0] / max(vals[1], 1))
    return default  # unresolvable: fall back to the configured bound


def _dtype_bytes(node: Optional[ast.AST]) -> int:
    if node is None:
        return 4
    chain = _attr_chain(node)
    if chain and chain[-1] in _DTYPE_BYTES:
        return _DTYPE_BYTES[chain[-1]]
    return 4  # unknown (e.g. pool.dtype): assume full-width f32


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _as_elements(node: Optional[ast.AST]) -> List[ast.AST]:
    if node is None:
        return []
    if isinstance(node, (ast.List, ast.Tuple)):
        return list(node.elts)
    return [node]


class VmemBudgetRule(Rule):
    id = "pallas-vmem"
    summary = ("per-kernel VMEM upper bound (2x blocked operands + scratch, "
               "worst-case dims) must fit the per-core cap")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        dims = ctx.config.assumed_dims
        default = ctx.config.default_dim
        cap = ctx.config.vmem_cap_bytes
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if not chain or chain[-1] != "pallas_call":
                continue
            parts: List[Tuple[str, int]] = []
            for label, spec in self._block_specs(ctx, node):
                nbytes = self._blockspec_bytes(spec, dims, default)
                if nbytes:
                    parts.append((label, 2 * nbytes))  # pipeline double-buffer
            for scratch in _as_elements(_kw(node, "scratch_shapes")):
                nbytes = self._scratch_bytes(scratch, dims, default)
                if nbytes:
                    parts.append(("scratch", nbytes))
            total = sum(b for _, b in parts)
            if total > cap:
                detail = " + ".join(f"{label}:{b // 1024}KiB"
                                    for label, b in parts)
                yield self.finding(
                    ctx, node,
                    f"kernel VMEM upper bound {total / 2**20:.1f} MiB exceeds "
                    f"the {cap / 2**20:.1f} MiB cap ({detail}); shrink block "
                    "shapes or raise --vmem-cap-bytes with a justification")
            yield from self._check_aliases(ctx, node)

    def _check_aliases(self, ctx: ModuleContext, call: ast.Call
                       ) -> Iterator[Finding]:
        """Validate an ``input_output_aliases`` dict literal statically."""
        aliases = _kw(call, "input_output_aliases")
        if not isinstance(aliases, ast.Dict):
            return
        in_specs = _as_elements(_kw(call, "in_specs"))
        out_specs = _as_elements(_kw(call, "out_specs"))
        n_out = len(out_specs) or len(_as_elements(_kw(call, "out_shape")))
        for k, v in zip(aliases.keys, aliases.values):
            if not (isinstance(k, ast.Constant) and isinstance(k.value, int)
                    and isinstance(v, ast.Constant)
                    and isinstance(v.value, int)
                    and k.value >= 0 and v.value >= 0):
                continue  # computed alias indices: not statically decidable
            if in_specs and k.value >= len(in_specs):
                yield self.finding(
                    ctx, k,
                    f"input_output_aliases names input {k.value} but only "
                    f"{len(in_specs)} in_specs exist; operand indices count "
                    "every input (SMEM blocks included)")
                continue
            if n_out and v.value >= n_out:
                yield self.finding(
                    ctx, v,
                    f"input_output_aliases names output {v.value} but only "
                    f"{n_out} outputs exist")
                continue
            if in_specs and out_specs:
                mem_in = self._memspace(ctx, in_specs[k.value])
                mem_out = self._memspace(ctx, out_specs[v.value])
                if mem_in and mem_out and mem_in != mem_out:
                    yield self.finding(
                        ctx, k,
                        f"aliased pair input {k.value} ({mem_in}) -> output "
                        f"{v.value} ({mem_out}) straddles memory spaces; an "
                        "alias names ONE buffer, so both specs must agree "
                        "(likely a miscounted operand index)")

    @staticmethod
    def _memspace(ctx: ModuleContext, el: ast.AST) -> Optional[str]:
        """The declared memory space of a BlockSpec element, if decidable."""
        if isinstance(el, ast.Name):
            el = VmemBudgetRule._resolve_local(ctx, el.id)
        if not isinstance(el, ast.Call):
            return None
        chain = _attr_chain(el.func)
        if not chain or chain[-1] != "BlockSpec":
            return None
        mem = _kw(el, "memory_space")
        if mem is None:
            return "VMEM"  # blocked specs default to the VMEM pipeline
        mchain = _attr_chain(mem)
        return mchain[-1] if mchain else None

    def _block_specs(self, ctx: ModuleContext, call: ast.Call
                     ) -> Iterator[Tuple[str, ast.Call]]:
        """Yield (label, BlockSpec call) for in/out specs, incl. grid_spec."""
        sources = [("in", _kw(call, "in_specs")), ("out", _kw(call, "out_specs"))]
        grid_spec = _kw(call, "grid_spec")
        if grid_spec is None and call.args:
            maybe = call.args[1] if len(call.args) > 1 else None
            if isinstance(maybe, ast.Call):
                grid_spec = maybe
        if isinstance(grid_spec, ast.Call):
            sources += [("in", _kw(grid_spec, "in_specs")),
                        ("out", _kw(grid_spec, "out_specs"))]
        elif isinstance(grid_spec, ast.Name):
            spec_def = self._resolve_local(ctx, grid_spec.id)
            if isinstance(spec_def, ast.Call):
                sources += [("in", _kw(spec_def, "in_specs")),
                            ("out", _kw(spec_def, "out_specs"))]
        for label, src in sources:
            for el in _as_elements(src):
                target = el
                if isinstance(el, ast.Name):
                    target = self._resolve_local(ctx, el.id)
                if isinstance(target, ast.Call):
                    tchain = _attr_chain(target.func)
                    if tchain and tchain[-1] == "BlockSpec":
                        yield label, target

    @staticmethod
    def _resolve_local(ctx: ModuleContext, name: str) -> Optional[ast.AST]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id == name:
                return node.value
        return None

    @staticmethod
    def _blockspec_bytes(spec: ast.Call, dims: Dict[str, int],
                         default: int) -> int:
        mem = _kw(spec, "memory_space")
        if mem is not None:
            mchain = _attr_chain(mem)
            if mchain and mchain[-1] in ("SMEM", "ANY"):
                return 0  # not a VMEM block
        if not spec.args:
            return 0  # whole-operand spec (memory decided by the compiler)
        shape = spec.args[0]
        if not isinstance(shape, (ast.Tuple, ast.List)):
            return 0
        n = 1
        for dim in shape.elts:
            n *= max(_eval_dim(dim, dims, default), 1)
        return n * 4  # BlockSpec carries no dtype; assume f32

    @staticmethod
    def _scratch_bytes(node: ast.AST, dims: Dict[str, int],
                       default: int) -> int:
        if not isinstance(node, ast.Call):
            return 0
        chain = _attr_chain(node.func)
        if not chain or chain[-1] != "VMEM":
            return 0  # SMEM scratch / semaphores don't consume VMEM
        if not node.args:
            return 0
        shape = node.args[0]
        if not isinstance(shape, (ast.Tuple, ast.List)):
            return 0
        n = 1
        for dim in shape.elts:
            n *= max(_eval_dim(dim, dims, default), 1)
        dtype = node.args[1] if len(node.args) > 1 else None
        return n * _dtype_bytes(dtype)


class DmaPairingRule(Rule):
    id = "pallas-dma"
    summary = ("every make_async_copy(...).start() needs a matching .wait() "
               "on the same semaphore expression in the module")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        started: Dict[str, ast.AST] = {}
        waited: Set[str] = set()
        copy_names: Dict[str, str] = {}   # var name -> semaphore expr
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if chain and chain[-1] == "make_async_copy":
                sem = self._sem_expr(node)
                use = self._immediate_use(ctx, node)
                if use == "start":
                    started.setdefault(sem, node)
                elif use == "wait":
                    waited.add(sem)
                else:
                    assigned = self._assigned_name(ctx, node)
                    if assigned:
                        copy_names[assigned] = sem
            elif isinstance(node.func, ast.Attribute) \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id in copy_names:
                sem = copy_names[node.func.value.id]
                if node.func.attr == "start":
                    started.setdefault(sem, node)
                elif node.func.attr == "wait":
                    waited.add(sem)
        for sem, node in started.items():
            if sem not in waited:
                yield self.finding(
                    ctx, node,
                    f"DMA started on semaphore `{sem}` is never awaited in "
                    "this module; add the matching .wait() (unwaited copies "
                    "race their consumer)")
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                chain = _attr_chain(node.func)
                if chain and chain[-1] == "pallas_call":
                    yield from self._check_sem_slots(ctx, node)

    def _check_sem_slots(self, ctx: ModuleContext, call: ast.Call
                         ) -> Iterator[Finding]:
        """Constant ``sem.at[i]`` must fit the declared DMA((k,)) shape."""
        scratch = _as_elements(_kw(call, "scratch_shapes"))
        if not scratch:
            return
        fn = self._kernel_def(ctx, call)
        if fn is None or fn.args.vararg is not None:
            return  # kernel not statically resolvable / *refs-style packing
        params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        if len(params) < len(scratch):
            return
        caps: Dict[str, int] = {}
        for name, decl in zip(params[-len(scratch):], scratch):
            cap = self._dma_capacity(decl)
            if cap is not None:
                caps[name] = cap
        for sub in ast.walk(fn):
            if not (isinstance(sub, ast.Subscript)
                    and isinstance(sub.value, ast.Attribute)
                    and sub.value.attr == "at"
                    and isinstance(sub.value.value, ast.Name)
                    and sub.value.value.id in caps):
                continue
            idx = sub.slice
            if isinstance(idx, ast.Constant) and isinstance(idx.value, int):
                cap = caps[sub.value.value.id]
                if not -cap <= idx.value < cap:
                    yield self.finding(
                        ctx, sub,
                        f"`{ast.unparse(sub)}` indexes past the declared "
                        f"SemaphoreType.DMA(({cap},)) capacity in kernel "
                        f"`{fn.name}`; an out-of-range slot aliases a "
                        "neighbouring semaphore (interpret mode hides it)")

    @staticmethod
    def _kernel_def(ctx: ModuleContext, call: ast.Call
                    ) -> Optional[ast.FunctionDef]:
        """Resolve pallas_call's kernel argument to its FunctionDef."""
        node: Optional[ast.AST] = call.args[0] if call.args else None
        for _ in range(4):   # Name -> local assign -> partial(...) -> Name
            if isinstance(node, ast.Name):
                for cand in ast.walk(ctx.tree):
                    if isinstance(cand, ast.FunctionDef) \
                            and cand.name == node.id:
                        return cand
                node = VmemBudgetRule._resolve_local(ctx, node.id)
            elif isinstance(node, ast.Call):
                chain = _attr_chain(node.func)
                if chain and chain[-1] == "partial" and node.args:
                    node = node.args[0]
                else:
                    return None
            else:
                return None
        return None

    @staticmethod
    def _dma_capacity(decl: ast.AST) -> Optional[int]:
        """The k of a literal ``pltpu.SemaphoreType.DMA((k,))`` scratch."""
        if not isinstance(decl, ast.Call):
            return None
        chain = _attr_chain(decl.func)
        if not chain or chain[-1] != "DMA" or "SemaphoreType" not in chain:
            return None
        if len(decl.args) != 1 \
                or not isinstance(decl.args[0], (ast.Tuple, ast.List)) \
                or len(decl.args[0].elts) != 1:
            return None
        dim = decl.args[0].elts[0]
        if isinstance(dim, ast.Constant) and isinstance(dim.value, int):
            return dim.value
        return None

    @staticmethod
    def _sem_expr(call: ast.Call) -> str:
        if len(call.args) >= 3:
            return ast.unparse(call.args[2])
        kw = _kw(call, "sem")
        return ast.unparse(kw) if kw is not None else "<none>"

    @staticmethod
    def _immediate_use(ctx: ModuleContext, call: ast.Call) -> Optional[str]:
        parent = ctx.parent(call)
        if isinstance(parent, ast.Attribute) and parent.attr in ("start",
                                                                 "wait"):
            return parent.attr
        return None

    @staticmethod
    def _assigned_name(ctx: ModuleContext, call: ast.Call) -> Optional[str]:
        parent = ctx.parent(call)
        if isinstance(parent, ast.Assign) and len(parent.targets) == 1 \
                and isinstance(parent.targets[0], ast.Name):
            return parent.targets[0].id
        return None
