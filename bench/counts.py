"""Operations and bytes the OTA-DSGD round requires, from its shapes.

Counted from the algorithm, the same whatever implements it: recomputation
(remat), padding and regenerated measurement matrices do not count.  The
arithmetic follows the analytic model of the repository's roofline script
(6 N tokens for a model's forward and backward, 2 s c per projected block,
(10 + 4 iters) d s for the blocked AMP decode).
"""
from __future__ import annotations

F32 = 4


def model_train_flops(n_params: int, tokens: int) -> float:
    """Forward and backward of a dense model: 6 N per token."""
    return 6.0 * n_params * tokens


def project_flops(n_blocks: int, s_block: int, c: int) -> float:
    """Forward blocked projection y_b = A_b x_b: 2 s c per block."""
    return 2.0 * n_blocks * s_block * c


def project_bytes(n_blocks: int, s_block: int, c: int) -> float:
    """x in and y out in f32; A is generated in the kernel, never read."""
    return float(F32 * n_blocks * (c + s_block))


def amp_blocked_flops(n_blocks: int, s_block: int, c: int,
                      iters: int) -> float:
    """Blocked AMP: per iteration one adjoint and one forward matvec
    (4 s c per block), plus 10 s c per block for the measurement matrix's
    generation and the least-squares debias."""
    return (10.0 + 4.0 * iters) * n_blocks * s_block * c


def amp_blocked_bytes(n_blocks: int, s_block: int, c: int) -> float:
    """y in, x-hat out, in f32."""
    return float(F32 * n_blocks * (s_block + c))


def dense_project_flops(s: int, d: int, vectors: int) -> float:
    """Dense projection A (s x d) of ``vectors`` gradients."""
    return 2.0 * s * d * vectors


def amp_dense_flops(s: int, d: int, iters: int) -> float:
    """Dense AMP: two matvecs per iteration and one for the debias."""
    return 2.0 * s * d * (2 * iters + 1)


def softmax_regression_flops(samples: int, dim: int, classes: int) -> float:
    """Forward and backward of a single-layer softmax classifier:
    logits (2 n dim C) and the weight gradient (2 n dim C)."""
    return 4.0 * samples * dim * classes


def softmax_eval_flops(samples: int, dim: int, classes: int) -> float:
    """Test loss and accuracy of a single-layer softmax classifier: the
    logits once for each (2 n dim C per pass)."""
    return 2.0 * 2.0 * samples * dim * classes


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
