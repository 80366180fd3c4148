"""Kernel contract declarations.

A :func:`kernel_contract` decorator sits on every device-kernel entry
point in the package — any ``pallas_call`` wrapper, the jitted XLA
kernels, the shard_map collectives, and the host-side dispatchers that
gate them — and states, in one checkable place, what the docstrings used
to promise:

  * block shapes, dtypes, and memory spaces (Pallas kinds), plus the
    worst-case configuration the dispatcher will admit;
  * the VMEM budget the footprint of those blocks must fit;
  * the trailing-dim tiling the TPU requires ((sublane, 128), sublane
    8/16/32 by itemsize);
  * grid/index-map in-bounds behavior;
  * whether inputs ride int31 relative timestamps, and which dispatcher
    predicate proves the span fits;
  * an ``example()`` of abstract inputs so ``jax.eval_shape`` can check
    the wrapper's output shapes/dtypes without a TPU (or a fully custom
    ``check()`` for kernels that need an axis/mesh context).

This module is imported by the hot kernel modules, so it stays
dependency-free and does nothing at runtime beyond attaching the
declaration and registering it; all verification lives in
``filodb_tpu.lint.rules_kernel`` and runs only under the linter.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Sequence, Tuple

VMEM_BYTES = 16 << 20          # per-core VMEM (v4/v5 class parts)

# minimum sublane count by dtype itemsize: trailing dims must tile to
# (sublane, 128)
SUBLANE_BY_ITEMSIZE = {1: 32, 2: 16, 4: 8}

VMEM, SMEM, HBM, ANY, SEM = "vmem", "smem", "hbm", "any", "semaphore"


@dataclass(frozen=True)
class Block:
    """One declared array block (input, output, or scratch).

    ``shape`` is the worst-case BLOCK shape resident on chip at once
    (double buffering spelled out in the shape, e.g. leading 2).
    ``array_shape`` + ``index_map`` (block-index convention, as in
    ``pl.BlockSpec``) opt the block into the grid-bounds check.
    ``tiled=False`` exempts a VMEM block from the (sublane, 128) check —
    scalars/params and 1-D vectors."""
    name: str
    shape: Tuple[int, ...]
    dtype: str = "float32"
    space: str = VMEM
    tiled: bool = True
    array_shape: Optional[Tuple[int, ...]] = None
    index_map: Optional[Callable] = None

    def itemsize(self) -> int:
        import numpy as np
        return int(np.dtype(self.dtype).itemsize)

    def nbytes(self) -> int:
        n = self.itemsize()
        for d in self.shape:
            n *= int(d)
        return n


@dataclass
class KernelContract:
    """The checked declaration attached to a kernel entry point."""
    name: str
    kind: str                          # pallas | jit | shard_map | dispatch
    fn: Callable = None
    module: str = ""
    qualname: str = ""
    grid: Optional[Tuple[int, ...]] = None
    blocks: Tuple[Block, ...] = ()
    scratch: Tuple[Block, ...] = ()
    outputs: Tuple[Block, ...] = ()
    vmem_budget: Optional[int] = None
    # inputs are int32 offsets relative to a base: the dispatcher
    # predicate named here must prove the whole span fits rel_time_bits
    rel_time_bits: Optional[int] = None
    span_guard: Optional[str] = None
    # example() -> (args, kwargs) of ShapeDtypeStructs/static values for
    # jax.eval_shape; expect(out) -> error string or None
    example: Optional[Callable[[], Tuple[tuple, dict]]] = None
    expect: Optional[Callable[[object], Optional[str]]] = None
    # fully custom abstract check (mesh/axis contexts): -> error or None
    check: Optional[Callable[[], Optional[str]]] = None
    notes: str = ""

    def all_vmem_blocks(self) -> Tuple[Block, ...]:
        return tuple(b for b in (*self.blocks, *self.scratch,
                                 *self.outputs) if b.space == VMEM)

    def vmem_footprint(self) -> int:
        return sum(b.nbytes() for b in self.all_vmem_blocks())


# (module, name) -> contract; keyed so re-execution of a module (tests,
# importlib.reload) replaces rather than duplicates
CONTRACTS: Dict[Tuple[str, str], KernelContract] = {}


def kernel_contract(name: str, *, kind: str,
                    grid: Optional[Tuple[int, ...]] = None,
                    blocks: Sequence[Block] = (),
                    scratch: Sequence[Block] = (),
                    outputs: Sequence[Block] = (),
                    vmem_budget: Optional[int] = None,
                    rel_time_bits: Optional[int] = None,
                    span_guard: Optional[str] = None,
                    example: Optional[Callable] = None,
                    expect: Optional[Callable] = None,
                    check: Optional[Callable] = None,
                    notes: str = ""):
    """Attach and register a :class:`KernelContract`.

    Stack OUTSIDE ``jax.jit`` (closest to the reader) so the registered
    callable is the jitted entry point the rest of the code calls."""
    def deco(fn):
        c = KernelContract(
            name=name, kind=kind, fn=fn,
            module=getattr(fn, "__module__", "") or "",
            qualname=getattr(fn, "__qualname__",
                             getattr(fn, "__name__", name)),
            grid=tuple(grid) if grid is not None else None,
            blocks=tuple(blocks), scratch=tuple(scratch),
            outputs=tuple(outputs), vmem_budget=vmem_budget,
            rel_time_bits=rel_time_bits, span_guard=span_guard,
            example=example, expect=expect, check=check, notes=notes)
        CONTRACTS[(c.module, name)] = c
        try:
            fn.__kernel_contract__ = c
        except (AttributeError, TypeError):   # e.g. functools.partial
            pass
        return fn
    return deco


def contracts_for_module(module: str):
    return [c for (m, _), c in sorted(CONTRACTS.items()) if m == module]
