"""Required work of the held experts' grouped products, from their shapes.

Taken from (rows, d_model, expert width F, held experts E) and the
itemsize of the operands, never from the kernels' tiles or padding.  A
MoE layer's held experts run a SwiGLU over the rows routed to them: three
grouped products, two of d_model -> F (gate, up) and one of F -> d_model
(down).  A product of m rows from k to n over E experts makes 2 m k n
operations and moves its rows in (m k), the experts' weights (E k n) and
its rows out (m n), each once.  The backward pass of each product makes
two of the same size: the rows' gradient (``moe_gmm`` against the
transposed weights: dout and weights in, m k out) and the weights'
gradient (``moe_tgmm``: rows and dout in, E k n out), so it is twice the
forward in operations and in bytes.  No recomputation is counted.

The least time of a call is the larger of operations over the bf16 peak
and bytes over the bandwidth.  At Moonlight's cut (768 rows an expert on
average, float32) the forward makes ~200 operations a byte, under the
v5e's 197e12 / 819e9 = 240: bound by bandwidth, near the ridge.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

# in the device trace the forward and rows' gradient kernels are named
# moe_gmm, the weights' gradient kernels moe_tgmm
KERNELS = ("moe_gmm", "moe_tgmm")


def _products(d_model: int, width: int) -> Tuple[Tuple[int, int], ...]:
    """(k, n) of the SwiGLU's three products."""
    return ((d_model, width), (d_model, width), (width, d_model))


def flops(m: int, d_model: int, width: int, experts: int
          ) -> Dict[str, float]:
    fwd = sum(2.0 * m * k * n for k, n in _products(d_model, width))
    return {"fwd": fwd, "bwd": 2.0 * fwd}


def bytes_moved(m: int, d_model: int, width: int, experts: int,
                itemsize: int) -> Dict[str, float]:
    fwd = sum(float(m * k + experts * k * n + m * n) * itemsize
              for k, n in _products(d_model, width))
    return {"fwd": fwd, "bwd": 2.0 * fwd}


def least_seconds(m: int, d_model: int, width: int, experts: int,
                  itemsize: int, peaks: Dict[str, float]) -> float:
    """The least time of one layer's held experts, forward and backward."""
    f = flops(m, d_model, width, experts)
    b = bytes_moved(m, d_model, width, experts, itemsize)
    return sum(max(f[p] / peaks["bf16_flops_per_s"],
                   b[p] / peaks["hbm_bytes_per_s"]) for p in ("fwd", "bwd"))


def step_least_seconds(calls: Iterable[Tuple[int, int, int, int]],
                       itemsize: int, peaks: Dict[str, float]) -> float:
    """The least time of one step's calls (``expert_calls`` of the
    architecture's step work)."""
    return sum(least_seconds(m, dm, w, e, itemsize, peaks)
               for m, dm, w, e in calls)
