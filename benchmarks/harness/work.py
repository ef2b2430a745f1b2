"""Operations and bytes from shapes: what the algorithm needs, whatever
implements it. Pure arithmetic on an ``Arch`` — no jax.

Conventions: a multiply-add is 2 FLOPs; recomputed operations do not
count; causal attention counts only the keys at or before the query.
"""

from __future__ import annotations

from .weights import Arch, global_leaves, layer_leaves


def _size(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def layer_params(a: Arch) -> int:
    return sum(_size(s) for _, s, _ in layer_leaves(a))


def layer_matmul_params(a: Arch) -> int:
    return sum(_size(s) for _, s, k in layer_leaves(a) if k == "w")


def n_params(a: Arch) -> int:
    return a.layers * layer_params(a) \
        + sum(_size(s) for _, s, _ in global_leaves(a))


def kv_bytes_per_token(a: Arch, itemsize: int = 2) -> int:
    """K and V of every layer for one position."""
    return 2 * a.kv_heads * a.head_dim * a.layers * itemsize


def train_flops_per_token(a: Arch, seq: int) -> float:
    """Forward + backward of one token of a ``seq``-long row: 6 FLOPs a
    matmul weight (every block and the head; the embedding is a
    gather), and causal attention's two matmuls forward and four
    backward over seq/2 keys on average."""
    dense = 6 * (a.layers * layer_matmul_params(a) + a.vocab * a.d)
    attn = 6 * 2 * (seq / 2) * a.heads * a.head_dim * a.layers
    return dense + attn


def flash_flops_per_step(a: Arch, rows: int, seq: int) -> float:
    """Causal attention's own work in one training step, forward (QK^T,
    PV) and backward (dV, dP, dQ, dK): six matmuls of seq x seq/2 x
    head_dim per head, per row, per layer. The forward's recomputation
    inside a flash backward is not counted."""
    return 6 * 2 * (seq * seq / 2) * a.head_dim * a.heads * rows * a.layers


def serve_flops(a: Arch, prompt_len: int, n_out: int) -> float:
    """Model FLOPs of serving one request: every position through every
    block (2 a weight, attention over the positions so far), and the
    head for each sampled token."""
    n = prompt_len + n_out - 1          # positions fed to the model
    dense = 2 * a.layers * layer_matmul_params(a) * n
    attn = 4 * a.heads * a.head_dim * a.layers * n * (n + 1) / 2
    return dense + attn + 2 * a.vocab * a.d * n_out


def serve_token_flops(a: Arch, position: int, sampled: bool) -> float:
    """One position's share of ``serve_flops``."""
    return 2 * a.layers * layer_matmul_params(a) \
        + 4 * a.heads * a.head_dim * a.layers * (position + 1) \
        + (2 * a.vocab * a.d if sampled else 0)


def decode_step_bytes(a: Arch, live_tokens: float, itemsize: int = 2) -> float:
    """Least bytes one decode step reads: every block's weights, the
    final norm and the head once (the embedding is a gather of a few
    rows), and the K/V of the ``live_tokens`` positions the batch
    attends to."""
    weights = a.layers * layer_params(a) + a.vocab * a.d + a.d
    return weights * itemsize + live_tokens * kv_bytes_per_token(a, itemsize)


def decode_step_flops(a: Arch, batch: float, live_tokens: float) -> float:
    return batch * (2 * a.layers * layer_matmul_params(a) + 2 * a.vocab * a.d) \
        + 4 * a.heads * a.head_dim * a.layers * live_tokens
