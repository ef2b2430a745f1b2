"""Operations and bytes from shapes: what the algorithm needs, whatever
implements it. Pure arithmetic on an ``Arch`` — no jax. What depends on
the family's mathematics (its cache, its attention, which weights a token
meets) is counted in the family's own file and forwarded here by name, so
that the readers call one place; what follows from the leaf lists alone
is counted here.

Conventions: a multiply-add is 2 FLOPs; recomputed operations do not
count; causal attention counts only the keys at or before the query.
"""

from __future__ import annotations

from .weights import family, global_leaves, layer_leaves


def _size(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def layer_params(a, layer: int = 0) -> int:
    return sum(_size(s) for _, s, _ in layer_leaves(a, layer))


def layer_matmul_params(a, layer: int = 0) -> int:
    return sum(_size(s) for _, s, k in layer_leaves(a, layer) if k == "w")


def params(a) -> int:
    """Every block's weights, whatever kinds the layers are of."""
    return sum(layer_params(a, i) for i in range(a.layers))


def matmul_params(a) -> int:
    return sum(layer_matmul_params(a, i) for i in range(a.layers))


def n_params(a) -> int:
    return params(a) + sum(_size(s) for _, s, _ in global_leaves(a))


def serve_flops(a, prompt_len: int, n_out: int) -> float:
    """Model FLOPs of serving one request: every position fed to the
    model, the head for each sampled token."""
    n = prompt_len + n_out - 1
    return sum(serve_token_flops(a, p, p >= prompt_len - 1) for p in range(n))


def kv_bytes_per_token(a, itemsize: int = 2) -> int:
    return family(a.family).kv_bytes_per_token(a, itemsize)


def train_flops_per_token(a, seq: int) -> float:
    return family(a.family).train_flops_per_token(a, seq)


def flash_flops_per_step(a, rows: int, seq: int) -> float:
    return family(a.family).flash_flops_per_step(a, rows, seq)


def serve_token_flops(a, position: int, sampled: bool) -> float:
    return family(a.family).serve_token_flops(a, position, sampled)


def decode_step_bytes(a, live_tokens: float, itemsize: int = 2) -> float:
    return family(a.family).decode_step_bytes(a, live_tokens, itemsize)


def decode_step_flops(a, batch: float, live_tokens: float) -> float:
    return family(a.family).decode_step_flops(a, batch, live_tokens)
