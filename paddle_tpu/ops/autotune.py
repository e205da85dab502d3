"""What the Pallas kernels of this package share: Mosaic's tile and VMEM
constants, the one clamp every kernel holds a block to, and the two gates
the model hot paths ask before they call a kernel.

Nothing here tunes anything.  Until PR 48 this module was the kernels'
measured tile search (``@autotune`` over a registry of eleven kernels: time
every candidate on the chip at first use, keep the winner in
``<checkout>/.cache/kernel_tuning.json``).  A race between candidates a few
percent apart draws differently in each cold checkout, so two checkouts of
one tree built different programs and three honest PRs were refused for a
tile they never wrote; the searches also cost a cold start a minute.  A
kernel's tile is now a rule of its arguments' shapes, written beside the
kernel from a table timed on the chip (``tools/tile_table_chip.py``,
``PERF.md`` section 6); an explicit ``block_*=`` wins over the rule.

The name stays for ``benchmarks/run.py``, which imports this module and
prints :func:`cache_path` and :func:`get_counters` (the engine's, of
``tuning.engine``: what the plan and serving searches cached and counted,
nothing for a kernel); the ``benchmark`` PR that drops those three lines
(``ROADMAP.md`` B10) renames it for what it holds.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from ..framework import device as _device
from ..framework.flags import flag
from ..tuning.engine import cache_path, get_counters  # noqa: F401  (B10)

__all__ = [
    "SUBLANE", "LANE", "I0", "VMEM_BYTES", "VMEM_BUDGET_FRAC", "clamp_tile",
    "blocks_or", "vmem_fits", "cache_path", "get_counters",
    "fused_epilogues_eligible", "mesh_admits_kernels",
]

# -- Mosaic tiling / VMEM constants ------------------------------------------
SUBLANE = 8      # f32 sublane tile; row blocks are multiples
LANE = 128       # lane tile; column blocks are multiples
VMEM_BYTES = 16 * 1024 * 1024  # per-core VMEM (v4/v5e/v5p all ~16 MB)
#: the zero every BlockSpec index map returns for a whole dim.  The package
#: turns x64 on, so a Python ``0`` there becomes an i64 — which Mosaic
#: cannot return from an index map (``func.return (i32, i64)``).
I0 = np.int32(0)
#: fraction of VMEM a kernel's resident blocks may claim — the rest is
#: double-buffering headroom for the pipelined DMA in/out streams
VMEM_BUDGET_FRAC = 0.7


def clamp_tile(block: int, n: int, multiple: int = SUBLANE) -> int:
    """``block`` held to a length-``n`` dimension: no longer than the
    dimension (a short one never pays a full-width padded tile), in whole
    Mosaic tiles of ``multiple``."""
    return -(-min(int(block), max(int(n), multiple)) // multiple) * multiple


def blocks_or(rule, *given):
    """The caller's explicit blocks, the ``rule``'s where it gave ``None``:
    an explicit block wins over the rule (for the chip tool behind a rule's
    table and for the tests that run every block)."""
    return tuple(int(r if g is None else g) for r, g in zip(rule, given))


def vmem_fits(nbytes: int, frac: float = VMEM_BUDGET_FRAC) -> bool:
    """True iff a kernel's resident VMEM blocks fit the budget."""
    return nbytes <= int(VMEM_BYTES * frac)


# -- model-integration gates -------------------------------------------------
def mesh_admits_kernels() -> bool:
    """``pallas_call`` has no GSPMD partitioning rule: JAX refuses to lower
    a Mosaic kernel inside ANY jit that spans more than one device
    ("Mosaic kernels cannot be automatically partitioned"), whichever axis
    is sharded — ``data`` as much as ``model``.  The model hot paths trace
    under the global mesh, so their kernel gates open only on a one-device
    mesh; multi-chip meshes keep the XLA paths until the kernels are
    wrapped in ``shard_map``."""
    from ..distributed.mesh import get_mesh

    return get_mesh().size == 1


def fused_epilogues_eligible(feature_dim: Optional[int] = None) -> bool:
    """Should a model hot path call the fused Pallas epilogues?  Mirrors
    the flash-attention gate: a real TPU backend (interpret mode loses),
    lane-aligned feature dim, and a mesh that admits kernels
    (:func:`mesh_admits_kernels`)."""
    if not flag("fused_epilogues") or not _device.on_tpu():
        return False
    if feature_dim is not None and feature_dim % LANE != 0:
        return False
    return mesh_admits_kernels()
