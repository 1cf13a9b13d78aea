"""Work of one ``acq_score`` call, as the algorithm needs it.

Per GPHP draw s (S of them) and anchor (m of them), against n train rows in
d dimensions with M heads:

* cross-gram k(x, X): a difference, a square and a sum per dimension (3d)
  and the Matérn-5/2 closed form (8) per row: n(3d + 8);
* the means: M dot products of length n: 2nM;
* σ² = amp² − ‖L⁻¹k*‖²: L⁻¹ is lower-triangular, so the solve needs
  n(n+1)/2 multiply-adds (n(n+1) flops) and the square-and-sum 2n more;
* EI's closed form: 20.

Bytes (float32, what the kernel reads and writes once): per draw the anchors
(m·d) and train rows (n·d), the lower triangle of L⁻¹ (n(n+1)/2), the head
alphas (M·n) and the signal variance (1); the row mask (n) once; the scores
(S·m).

Counts use the call's own shapes: n is the posterior's row bucket, m the
anchors scored, d the encoded dimension; padding that the kernel adds to
fit its tiles is not work.
"""

from __future__ import annotations

# the kernel's HLO instruction names in a device trace
TRACE_NAMES = ("acq_score_pallas", "acq_score")
BYTES_PER_ELEMENT = 4  # float32 on the chip
CLOSED_FORM_FLOPS = 20


def flops(s: int, m: int, n: int, d: int, heads: int = 1) -> int:
    per_anchor = n * (3 * d + 8) + 2 * n * heads + n * (n + 1) + 2 * n
    return s * m * (per_anchor + CLOSED_FORM_FLOPS)


def bytes_moved(s: int, m: int, n: int, d: int, heads: int = 1) -> int:
    per_draw = m * d + n * d + n * (n + 1) // 2 + heads * n + 1
    return BYTES_PER_ELEMENT * (s * per_draw + n + s * m)


def calls(shape: dict):
    """The kernel calls one ``optimize_acquisition`` makes, as
    (s, m, n, d, heads): the anchor sweep and the re-rank of the refined
    points."""
    s, n, d = shape["s"], shape["n"], shape["d"]
    return [(s, shape["num_anchors"], n, d, 1), (s, shape["num_refine"], n, d, 1)]


def least_seconds(call, peaks) -> float:
    """The chip's least time for one call: the larger of its flops over the
    peak FLOP/s and its bytes over the peak bytes/s."""
    return max(flops(*call) / peaks["flops_per_s"],
               bytes_moved(*call) / peaks["bytes_per_s"])
