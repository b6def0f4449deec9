"""Operations and bytes of the Mamba2 model and its SSD scan, from shapes.

These count what the algorithm needs, not what a kernel happens to do:
a kernel that recomputes work gets no credit for it, so its roofline
share shows the waste.  ``z`` is ``bench.weights.dims`` of a configuration.
"""

from __future__ import annotations


def ssd_flops(S: int, H: int, P: int, G: int, N: int, Q: int) -> int:
    """One sequence of the chunked SSD scan (chunk Q, S a multiple of Q).

    Per chunk and head: 2Q²P for the masked (C·Bᵀ ∘ L)·x product, 2QNP for
    the entering state read out by C, 2QNP for the chunk's state B·xᵀ.
    Per chunk and group: 2Q²N for C·Bᵀ, which the heads of a group share.
    """
    nc = S // Q
    return nc * (H * (2 * Q * Q * P + 4 * Q * N * P) + G * 2 * Q * Q * N)


def ssd_bytes(S: int, H: int, P: int, G: int, N: int, act_bytes: int = 2) -> int:
    """Least HBM traffic of one sequence: x read and y written, B and C
    read (activation dtype), dt read (f32), A and D read, the final state
    written (f32)."""
    return (2 * S * H * P + 2 * S * G * N) * act_bytes + S * H * 4 + 2 * H * 4 + H * P * N * 4


def ssd_min_time(S: int, z: dict, flops_peak: float, bw_peak: float) -> float:
    """Least seconds on the chip for one SSD call: the larger of its FLOPs
    over the peak and its bytes over the bandwidth."""
    f = ssd_flops(S, z["H"], z["P"], z["G"], z["N"], min(z["chunk"], S))
    return max(f / flops_peak, ssd_bytes(S, z["H"], z["P"], z["G"], z["N"]) / bw_peak)


def block_flops_per_token(z: dict) -> int:
    """Dense work of one block per token: the five input projections, the
    depthwise conv, the out projection."""
    d, di, H, GN = z["d"], z["di"], z["H"], z["G"] * z["N"]
    return 2 * d * (2 * di + 2 * GN + H) + 2 * z["K"] * z["conv"] + 2 * di * d


def head_flops(z: dict) -> int:
    """Output head at one position, over the real vocabulary."""
    return 2 * z["d"] * z["V"]


def prefill_flops(S: int, z: dict) -> int:
    """One prompt of S tokens: every block over S tokens (SSD in its chunked
    form) and the head at the last position, which is all prefill reads."""
    ssd = ssd_flops(S, z["H"], z["P"], z["G"], z["N"], min(z["chunk"], S))
    return z["L"] * (S * block_flops_per_token(z) + ssd) + head_flops(z)


def decode_flops_per_token(z: dict) -> int:
    """One token through the recurrent step: state decay and update (4HPN),
    read-out (2HPN), and the head."""
    step = 6 * z["H"] * z["P"] * z["N"]
    return z["L"] * (block_flops_per_token(z) + step) + head_flops(z)

