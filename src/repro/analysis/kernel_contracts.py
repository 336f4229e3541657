"""Layer 2: Pallas kernel contract checker (DESIGN.md §15).

Pure stdlib-`ast` checks over the paged kernel sources — the contracts
that make the kernels lower natively on TPU and stream pages at HBM
speed (DESIGN.md §10) are all *structural*, so they are checkable
without tracing:

  KC101  every BlockSpec must either carry an explicit block shape
         (blocked VMEM operand) or be `memory_space=ANY` (HBM-resident
         pool, DMA'd page-by-page). A shapeless spec in any other
         memory space maps the WHOLE operand into the grid step.
  KC102  scalar-prefetch arity: the `pl.pallas_call(...)(<args>)`
         invocation must pass exactly `num_scalar_prefetch +
         len(in_specs)` operands, and the kernel body's positional
         parameter count must equal prefetch + in_specs + outputs +
         scratch_shapes — a silent mismatch shifts every ref one slot.
         A kernel taking `*refs` (the quantized/fp dual-layout bodies,
         §16: the ref tuple depends on whether scale rows ride along)
         instead satisfies the contract when its NAMED positionals do
         not exceed the implied count — the vararg absorbs the
         dtype-dependent tail.
  KC103  a `make_async_copy` that is created but never `.start()`ed or
         never `.wait()`ed: an un-awaited DMA is a read of garbage, an
         un-started one deadlocks the semaphore.
  KC104  issue-before-fold ordering: the first `.start()` must precede
         the first `.wait()` (the double-buffer warm-up), otherwise
         the pipeline serializes (or deadlocks on real hardware).
  KC105  wait-before-use: no subscript read of a buffer handed to a
         page walk (`double_buffered_page_walk`,
         `double_buffered_block_walk`) before the walk call returns —
         the landing buffers hold garbage until the walk's wait.
  KC106  a grid spec with ANY-space operands is a DMA kernel and must
         declare `pltpu.SemaphoreType.DMA` scratch.

Checks run per *top-level* function (nested `pl.when` bodies and copy
factories attribute to their enclosing kernel). `check_kernel_file` is
reusable on fixture files; `check_kernel_contracts` applies it to the
repo's kernel sources.
"""

from __future__ import annotations

import ast
import os
from typing import List, Optional, Sequence

from .findings import Finding

#: the kernel sources under contract, relative to the repo root
KERNEL_FILES = (
    "src/repro/kernels/paged_common.py",
    "src/repro/kernels/paged_attention.py",
    "src/repro/kernels/paged_prefill.py",
)


#: the shared page walks whose buffer arguments KC105 guards
_PAGE_WALKS = ("double_buffered_page_walk", "double_buffered_block_walk")


def _func_name(call: ast.Call) -> str:
    """Dotted name of a call's target ('' when not a name/attr chain)."""
    try:
        return ast.unparse(call.func)
    except Exception:
        return ""


def _calls(node: ast.AST):
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            yield n


def _spec_has_shape(call: ast.Call) -> bool:
    if any(isinstance(a, ast.Tuple) for a in call.args):
        return True
    return any(kw.arg == "block_shape" for kw in call.keywords)


def _spec_memory_space(call: ast.Call) -> Optional[str]:
    for kw in call.keywords:
        if kw.arg == "memory_space":
            try:
                return ast.unparse(kw.value).rsplit(".", 1)[-1]
            except Exception:
                return "?"
    return None


def _kwarg(call: ast.Call, name: str):
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _positional_arity(fn: ast.FunctionDef) -> int:
    return len(fn.args.posonlyargs) + len(fn.args.args)


class _FunctionChecker:
    def __init__(self, rel: str, fn: ast.FunctionDef,
                 module_funcs: dict):
        self.rel = rel
        self.fn = fn
        self.module_funcs = module_funcs
        self.findings: List[Finding] = []

    def err(self, rule: str, line: int, msg: str):
        self.findings.append(Finding(rule, self.rel, line, "error", msg))

    def run(self) -> List[Finding]:
        self._check_dma_ordering()
        self._check_walk_buffer_reads()
        for call in _calls(self.fn):
            if _func_name(call).endswith("PrefetchScalarGridSpec"):
                self._check_grid_spec(call)
        return self.findings

    # -- KC103 / KC104 ------------------------------------------------------

    def _check_dma_ordering(self):
        creates, starts, waits = [], [], []
        for call in _calls(self.fn):
            name = _func_name(call)
            if name.endswith("make_async_copy"):
                creates.append(call.lineno)
            elif isinstance(call.func, ast.Attribute):
                if call.func.attr == "start" and not call.args:
                    starts.append(call.lineno)
                elif call.func.attr == "wait" and not call.args:
                    waits.append(call.lineno)
        if not creates:
            return
        if not starts or not waits:
            missing = "started" if not starts else "awaited"
            self.err(
                "KC103", creates[0],
                f"`{self.fn.name}` creates an async copy that is never "
                f"{missing} (`make_async_copy` without "
                f"`.{'start' if not starts else 'wait'}()`)",
            )
            return
        if min(starts) > min(waits):
            self.err(
                "KC104", min(waits),
                f"`{self.fn.name}` waits on a DMA (line {min(waits)}) "
                f"before the first `.start()` (line {min(starts)}) — "
                "the issue-before-fold warm-up is inverted",
            )

    # -- KC105 --------------------------------------------------------------

    def _check_walk_buffer_reads(self):
        walk_call = None
        for call in _calls(self.fn):
            if _func_name(call).endswith(_PAGE_WALKS):
                walk_call = call
                break
        if walk_call is None:
            return
        buf_names = {
            a.id for a in walk_call.args if isinstance(a, ast.Name)
        }
        for node in ast.walk(self.fn):
            if (
                isinstance(node, ast.Subscript)
                and isinstance(node.value, ast.Name)
                and node.value.id in buf_names
                and isinstance(node.ctx, ast.Load)
                and node.lineno < walk_call.lineno
            ):
                self.err(
                    "KC105", node.lineno,
                    f"`{self.fn.name}` reads `{node.value.id}[...]` "
                    f"before the page walk at line {walk_call.lineno} "
                    "waits on its DMA — the landing buffer holds "
                    "garbage until the walk returns",
                )

    # -- KC101 / KC102 / KC106 ----------------------------------------------

    def _check_grid_spec(self, spec: ast.Call):
        in_specs = _kwarg(spec, "in_specs")
        out_specs = _kwarg(spec, "out_specs")
        scratch = _kwarg(spec, "scratch_shapes")
        n_prefetch_node = _kwarg(spec, "num_scalar_prefetch")
        n_prefetch = (
            n_prefetch_node.value
            if isinstance(n_prefetch_node, ast.Constant) else None
        )

        spec_lists = []
        if isinstance(in_specs, ast.List):
            spec_lists.append(("in_specs", in_specs.elts))
        if isinstance(out_specs, ast.List):
            spec_lists.append(("out_specs", out_specs.elts))
        elif isinstance(out_specs, ast.Call):
            spec_lists.append(("out_specs", [out_specs]))
        any_count = 0
        for which, elts in spec_lists:
            for i, elt in enumerate(elts):
                if not isinstance(elt, ast.Call):
                    continue
                space = _spec_memory_space(elt)
                if space == "ANY":
                    any_count += 1
                    continue
                if not _spec_has_shape(elt):
                    self.err(
                        "KC101", elt.lineno,
                        f"{which}[{i}] has neither a block shape nor "
                        f"memory_space=ANY (space={space}) — the whole "
                        "operand gets mapped into VMEM every grid step",
                    )

        if any_count and isinstance(scratch, ast.List):
            has_dma_sem = any(
                "SemaphoreType" in _func_name(c)
                for e in scratch.elts for c in _calls(e)
                if isinstance(e, (ast.Call, ast.Attribute))
            ) or any(
                "SemaphoreType" in ast.unparse(e) for e in scratch.elts
            )
            if not has_dma_sem:
                self.err(
                    "KC106", scratch.lineno,
                    f"`{self.fn.name}` maps ANY-space operands (DMA "
                    "kernel) but declares no `pltpu.SemaphoreType.DMA` "
                    "scratch semaphore",
                )

        if n_prefetch is None or not isinstance(in_specs, ast.List):
            return
        n_in = len(in_specs.elts)
        n_out = (
            len(out_specs.elts) if isinstance(out_specs, ast.List) else 1
        )
        n_scratch = (
            len(scratch.elts) if isinstance(scratch, ast.List) else 0
        )

        # the pallas_call(...)( <operands> ) invocation in this function
        for call in _calls(self.fn):
            inner = call.func
            if not (
                isinstance(inner, ast.Call)
                and _func_name(inner).endswith("pallas_call")
            ):
                continue
            n_invoke = len(call.args)
            if n_invoke != n_prefetch + n_in:
                self.err(
                    "KC102", call.lineno,
                    f"pallas_call invocation passes {n_invoke} operands "
                    f"but the grid spec declares num_scalar_prefetch="
                    f"{n_prefetch} + {n_in} in_specs",
                )
            kernel_fn = self._resolve_kernel(inner)
            if kernel_fn is not None:
                got = _positional_arity(kernel_fn)
                want = n_prefetch + n_in + n_out + n_scratch
                if kernel_fn.args.vararg is not None:
                    # dual-layout body (`*refs`, §16): the vararg takes
                    # the dtype-dependent tail; only an overshoot of the
                    # named positionals can shift refs out of slot
                    if got > want:
                        self.err(
                            "KC102", kernel_fn.lineno,
                            f"kernel `{kernel_fn.name}` names {got} "
                            f"positional refs before `*"
                            f"{kernel_fn.args.vararg.arg}` but the grid "
                            f"spec implies at most {want} ({n_prefetch} "
                            f"prefetch + {n_in} in + {n_out} out + "
                            f"{n_scratch} scratch)",
                        )
                elif got != want:
                    self.err(
                        "KC102", kernel_fn.lineno,
                        f"kernel `{kernel_fn.name}` takes {got} "
                        f"positional refs but the grid spec implies "
                        f"{want} ({n_prefetch} prefetch + {n_in} in + "
                        f"{n_out} out + {n_scratch} scratch)",
                    )

    def _resolve_kernel(self, pallas_call: ast.Call
                        ) -> Optional[ast.FunctionDef]:
        """The kernel FunctionDef behind pallas_call's first argument —
        either a module function name or a local
        `X = functools.partial(F, ...)` binding."""
        if not pallas_call.args:
            return None
        target = pallas_call.args[0]
        name = target.id if isinstance(target, ast.Name) else None
        if name is None:
            return None
        for node in ast.walk(self.fn):
            if not isinstance(node, ast.Assign):
                continue
            if not any(
                isinstance(t, ast.Name) and t.id == name
                for t in node.targets
            ):
                continue
            v = node.value
            if (
                isinstance(v, ast.Call)
                and _func_name(v).endswith("partial")
                and v.args
                and isinstance(v.args[0], ast.Name)
            ):
                name = v.args[0].id
                break
        return self.module_funcs.get(name)


def check_kernel_file(path: str, rel: Optional[str] = None
                      ) -> List[Finding]:
    """All kernel-contract findings for one source file."""
    rel = rel or path
    with open(path) as fh:
        tree = ast.parse(fh.read(), filename=path)
    module_funcs = {
        n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)
    }
    findings: List[Finding] = []
    for fn in module_funcs.values():
        findings.extend(_FunctionChecker(rel, fn, module_funcs).run())
    return findings


def check_kernel_contracts(
    root: str, files: Optional[Sequence[str]] = None
) -> List[Finding]:
    """Kernel contracts over the repo's paged kernel sources (missing
    files are skipped so fixture repos can check a subset)."""
    findings: List[Finding] = []
    for rel in (files or KERNEL_FILES):
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            continue
        findings.extend(check_kernel_file(path, rel))
    return findings
