"""Operations and HBM bytes of one call of each Pallas kernel, from its
operand shapes.

Copied from the program's ``repro/kernels/costs.py`` formulas (keyed by
``pallas_call`` name, operands in the kernel wrapper's order, scalar
prefetch first) so that no later change to the program moves the
yardstick. FLOPs follow the matmul convention (2 x output elements x
contracted length); bytes are HBM traffic, and for the paged kernels only
the pages gathered, never the whole pool.
"""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Shape:
    """One operand or result: its dims and its bytes per element."""
    dims: tuple
    itemsize: int

    @property
    def elems(self) -> int:
        return math.prod(self.dims) if self.dims else 1

    @property
    def bytes(self) -> int:
        return self.elems * self.itemsize

    def row_bytes(self) -> float:
        return self.bytes / max(self.dims[0], 1)


def _io_bytes(out: Shape, ops) -> float:
    return float(out.bytes + sum(o.bytes for o in ops))


def chunk_attention(out: Shape, ops) -> dict:
    # q (B,C,H,dh), k (B,Sk,Hkv,dh), v, q_positions, k_positions
    sk = ops[1].dims[1]
    return {"flops": 4.0 * ops[0].elems * sk, "bytes": _io_bytes(out, ops)}


def paged_decode_attention(out: Shape, ops) -> dict:
    # page_map (B,n_pp), t (B,) [scalar prefetch], q (B,Hkv,g,dh),
    # k_pool (n_pages,p_sz,Hkv,dh), v_pool, pos_pool (n_pages,1,p_sz)
    b, n_pp = ops[0].dims
    p_sz = ops[3].dims[1]
    gathered = b * n_pp * (2.0 * ops[3].row_bytes() + ops[5].row_bytes())
    return {"flops": 4.0 * ops[2].elems * n_pp * p_sz,
            "bytes": float(ops[0].bytes + ops[1].bytes + ops[2].bytes
                           + out.bytes + gathered)}


def copy_pages(out: Shape, ops) -> dict:
    # src_dst table (2,n) [scalar prefetch], pool (n_pages, ...)
    n_copies = ops[0].dims[-1]
    return {"flops": 0.0,
            "bytes": float(ops[0].bytes + 2.0 * n_copies
                           * ops[1].row_bytes())}


KERNELS = {"chunk_attention": chunk_attention,
           "paged_decode_attention": paged_decode_attention,
           "copy_pages": copy_pages}


def least_seconds(cost: dict, peak: dict) -> float:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bandwidth."""
    return max(cost["flops"] / peak["bf16_flops_s"],
               cost["bytes"] / peak["hbm_bytes_s"])
