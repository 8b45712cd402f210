"""Operations and bytes that each GNN op needs, from LOGICAL shapes.

A count here is what the algorithm needs, never what an implementation
happens to move: a gathered row is read once however many edges use it,
a 32-wide head is 32 wide even where a kernel pads it to a 128-lane
tile, and a padded row or a masked fanout slot does no work.  So a
kernel that stops padding or re-reading raises its share, the count
does not change with the code under it, and no implementation can read
above 100% of a roofline built from it.

Per layer graph (fixed fanout ``F`` over ``n`` target rows, ``E``
sampled edges = masked-in slots, ``src_rows`` distinct source rows that
some edge reads, ``active`` target rows with at least one edge):

  gemm            2*n*d_in*d_out FLOPs; X, W read and the product written
  spmm / attend   2*E*d FLOPs; each read source row once, each edge's id
                  and weight, the (n, F) bool mask, the (n, d) output
  gat_attention   2*E*d FLOPs (the per-head dots; the softmax is not
                  counted, which can only lower the share); q of the
                  active rows, k of the read rows, ids, mask, and the
                  (n, F, heads) normalized scores written

Which ops one epoch of a model runs is the model file's
(``models/<name>.py``: ``epoch_calls``, ``epoch_min_bytes``), built
from these counters.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import numpy as np

F32 = 4
I32 = 4
BOOL = 1


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def least_s(self, peak_flops: float, peak_bytes_s: float) -> float:
        """The least time the chip could take: the larger of the
        compute bound and the memory bound."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes_s)


@dataclasses.dataclass(frozen=True)
class GraphShape:
    """The logical shape of one sampled layer graph."""
    n: int          # target rows
    fanout: int
    edges: int      # masked-in slots
    src_rows: int   # distinct source rows some edge reads
    active: int     # target rows with at least one edge

    @classmethod
    def of(cls, nbr: np.ndarray, mask: np.ndarray) -> "GraphShape":
        n, fanout = nbr.shape
        read = np.bincount(nbr[mask].astype(np.int64), minlength=n)
        return cls(n=n, fanout=fanout, edges=int(mask.sum()),
                   src_rows=int(np.count_nonzero(read)),
                   active=int(np.count_nonzero(mask.any(axis=1))))


def gemm(n: int, d_in: int, d_out: int) -> Work:
    return Work(2.0 * n * d_in * d_out,
                F32 * (n * d_in + d_in * d_out + n * d_out))


def _edge_bytes(g: GraphShape) -> float:
    return I32 * g.edges + BOOL * g.n * g.fanout


def spmm(g: GraphShape, d: int) -> Work:
    """out[i] = sum_f w[i,f] * h[nbr[i,f]] over the masked-in slots."""
    return Work(2.0 * g.edges * d,
                F32 * g.src_rows * d + _edge_bytes(g) + F32 * g.edges
                + F32 * g.n * d)


def gat_attention(g: GraphShape, d: int, heads: int) -> Work:
    """alpha[i,f,h] = softmax_f(<q_h[i], k_h[nbr[i,f]]> / sqrt(d/heads))."""
    return Work(2.0 * g.edges * d,
                F32 * g.active * d + F32 * g.src_rows * d + _edge_bytes(g)
                + F32 * g.n * g.fanout * heads)


def least_s(calls: Sequence[Tuple[str, Work]], kernel: str,
            peak_flops: float, peak_bytes_s: float) -> float:
    """Sum of each call's own least time (calls do not share a bound)."""
    return sum(w.least_s(peak_flops, peak_bytes_s)
               for name, w in calls if name == kernel)


def epoch_flops(calls: Sequence[Tuple[str, Work]]) -> float:
    return sum(w.flops for _, w in calls)


def fused_epoch_bytes(graphs: Sequence[GraphShape], d: int, weights: int
                      ) -> float:
    """The least HBM traffic of one epoch, each layer fused whole: X read
    once, each layer graph read once, each layer's ``weights`` (d, d)
    weights read once, each intermediate embedding written and read back
    once, the final one written once."""
    n = graphs[0].n
    total = F32 * n * d + F32 * n * d
    for l, g in enumerate(graphs):
        total += _edge_bytes(g) + F32 * weights * d * d
        if l < len(graphs) - 1:
            total += 2 * F32 * n * d
    return total
