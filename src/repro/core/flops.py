"""FLOPs accounting: the paper's Eq. 3 model (section 3.5).

``sliding_window_flops`` / ``dti_flops`` / ``flops_reduction_*`` are the
paper's own approximation, used to validate Eq. 3 and the 92% claim. The
chip benchmark's exact per-step accounts live in ``bench/accounts/``.
"""
from __future__ import annotations


# ---------------------------------------------------------------------------
# Paper Eq. 3 (section 3.5)
# ---------------------------------------------------------------------------

def sliding_window_flops(m: int, n: int, N: int, d: int, L: int) -> float:
    """(m - n) prompts x 2L x (N^2 d + N d^2)."""
    return (m - n) * 2 * L * (N * N * d + N * d * d)


def dti_flops(m: int, k: int, N: int, K: int, d: int, L: int) -> float:
    """m/k prompts x 2L x ((N+K) N d + (N+K) d^2)."""
    return (m / k) * 2 * L * ((N + K) * N * d + (N + K) * d * d)


def flops_reduction_exact(m: int, n: int, k: int, N: int, K: int) -> float:
    return (N * k * (m - n)) / (m * (N + K))


def flops_reduction_approx(N: int, K: int, k: int) -> float:
    """Paper Eq. 3: N*k / (N+K)."""
    return N * k / (N + K)


__all__ = ["sliding_window_flops", "dti_flops", "flops_reduction_exact",
           "flops_reduction_approx"]
