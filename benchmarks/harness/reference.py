"""The plain reference: the forward pass, the training loss, its
gradients and AdamW, in straightforward ``jax.numpy`` float32 at
``highest`` matmul precision. No kernels, no cache, no batching, and
nothing imported from ``tony_tpu``: weights come from ``weights.leaf``.

One layer's mathematics (``block``), the final norm and the head
(``logits``) are the model family's, written from its published
equations in ``benchmarks/families/<model_type>.py``; everything here is
general over them and over the family's leaf lists: a row goes through
the embedding (the global leaf ``embed``, a gather), every block in
turn, then ``logits``. Both halves go by ``weights.runs``, the
consecutive layers of one KIND (one run for a family that names no
kinds): the serving walk compiles one layer program a kind, with the
layer's index traced; the training tree holds each run's leaves stacked,
and ``row_loss`` scans one run after the other.

``quant="int8"`` (or ``"fp8"``, e4m3) is the CONTROL: the same mathematics
with every dense layer computed in that type, forward and backward
(operands and incoming cotangents rounded, symmetric, scaled by the absmax
over the contracted dims) — the precisions below the bf16 the
configurations state; a family's ``block`` takes its matmuls through
``dense`` here so that the control reaches them. The benchmark's runs never
use it; ``tests/`` and ``control.py`` do.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST


def _fq(x, axis, quant):
    """``x`` rounded to ``quant``: int8 or fp8 (e4m3) scaled by the absmax
    along ``axis``; ``bf16`` is plain rounding to 8 significand bits (a
    witness of what bf16 operands alone cost, not a control —
    ``reduce_precision``, because XLA may elide a convert pair)."""
    if quant == "bf16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if quant == "int8":
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _qmatmul(x, w, quant):
    """[T, K] @ [K, M] in the lower precision, forward AND backward (as a
    training path in that precision would run): operands rounded along
    K, the incoming cotangent along M."""
    return jnp.matmul(_fq(x, -1, quant), _fq(w, 0, quant), precision=HIGHEST)


def _qmatmul_fwd(x, w, quant):
    xq, wq = _fq(x, -1, quant), _fq(w, 0, quant)
    return jnp.matmul(xq, wq, precision=HIGHEST), (xq, wq)


def _qmatmul_bwd(quant, res, g):
    xq, wq = res
    gq = _fq(g, -1, quant)
    return (jnp.matmul(gq, wq.T, precision=HIGHEST),
            jnp.matmul(xq.T, gq, precision=HIGHEST))


_qmatmul.defvjp(_qmatmul_fwd, _qmatmul_bwd)


def dense(x, w, n: int = 1, bias=None, quant: str = ""):
    """Contract the last ``n`` dims of ``x`` with the first ``n`` of
    ``w``."""
    if quant:
        lead, out = x.shape[:x.ndim - n], w.shape[n:]
        k = 1
        for d in w.shape[:n]:
            k *= d
        y = _qmatmul(x.reshape(-1, k), w.reshape(k, -1), quant)
        y = y.reshape(*lead, *out)
    else:
        y = jnp.tensordot(x, w, axes=n, precision=HIGHEST)
    return y if bias is None else y + bias


def _block(a, kind: str):
    """The family's ``block`` for layers of ``kind`` as ``(p, x, quant)``;
    only a family that names kinds is told which."""
    F = W.family(a.family)
    if hasattr(F, "layer_kind"):
        return lambda p, x, quant: F.block(a, p, x, quant, kind=kind)
    return lambda p, x, quant: F.block(a, p, x, quant)


# ---------------------------------------------------------------- serving

@functools.partial(jax.jit, static_argnums=(0, 4, 5, 6))
def _serve_layer(a, x, key, layer, dtype, quant, kind):
    # static in the kind, traced in the index: one program a kind
    p = {n: v.astype(jnp.float32)
         for n, v in W.layer_weights(a, key, layer, dtype, kind).items()}
    block = _block(a, kind)
    return jax.lax.map(lambda row: block(p, row, quant), x)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _serve_embed(a, key, tokens, dtype):
    return W.leaf(a, key, -1, "embed", dtype).astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _serve_head(a, x, key, pos, target, dtype, quant):
    g = {n: v.astype(jnp.float32)
         for n, v in W.global_weights(a, key, dtype).items()
         if n != "embed"}
    h = jnp.take_along_axis(x, pos[:, :, None], axis=1)  # [N, P, D]
    logits = W.family(a.family).logits(a, g, h, quant)
    best = jnp.max(logits, -1)
    at = jnp.take_along_axis(logits, target[:, :, None], -1)[..., 0]
    return best, at, jnp.argmax(logits, -1)


def serve_logits(a, seed: int, tokens, pos, target, *,
                 dtype=jnp.bfloat16, quant: str = ""):
    """Full forward of ``tokens`` [N, L] (right-padded; causal, so the
    padding never reaches a real position) with the served weights
    (``dtype`` values, float32 arithmetic), layer by layer so that one
    layer's weights live at a time. At each ``pos`` [N, P]: the largest
    logit, the logit of ``target`` [N, P], and the argmax."""
    key = W.root_key(seed)
    x = _serve_embed(a, key, jnp.asarray(tokens), dtype)
    for kind, first, stop in W.runs(a):
        for layer in range(first, stop):
            x = _serve_layer(a, x, key, jnp.int32(layer), dtype, quant, kind)
    return _serve_head(a, x, key, jnp.asarray(pos), jnp.asarray(target),
                       dtype, quant)


# --------------------------------------------------------------- training

def init_train_params(a, seed: int) -> dict:
    """float32 master weights: globals under ``"g"``, and under ``"l"``
    one dict a run (``weights.runs``) of its block leaves, each stacked
    over the run's layers."""
    return _first_params(a, W.root_key(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def _first_params(a, key) -> dict:
    # the key is an ARGUMENT: a constant would make every seed a new
    # program to compile
    layers = [W.layer_weights(a, key, i, jnp.float32)
              for i in range(a.layers)]
    return {"g": W.global_weights(a, key, jnp.float32),
            "l": [{n: jnp.stack([lw[n] for lw in layers[first:stop]])
                   for n in layers[first]}
                  for _, first, stop in W.runs(a)]}


def row_hidden(a, params: dict, row, quant: str = ""):
    """One row [S] through the embedding and every block, run by run:
    [S, d] (float32)."""
    x = params["g"]["embed"][row]
    for (kind, _, _), run in zip(W.runs(a), params["l"]):
        block = _block(a, kind)

        @jax.checkpoint
        def body(x, p):
            return block(p, x, quant), None

        x, _ = jax.lax.scan(body, x, run)   # traced here, with this block
    return x


def row_loss(a, params: dict, row, quant: str = ""):
    """Sum of next-token cross-entropies of one row [S] (float32)."""
    x = row_hidden(a, params, row, quant)
    logits = W.family(a.family).logits(a, params["g"], x[:-1], quant)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.sum(jnp.take_along_axis(logp, row[1:, None], -1))


def loss_and_grads(a, params: dict, batch, quant: str = ""):
    """Mean loss over every target of ``batch`` [B, S] and its gradient,
    one row at a time (gradient accumulation: the mean is linear)."""
    n_targets = batch.shape[0] * (batch.shape[1] - 1)

    def total(p):
        return jnp.sum(jax.lax.map(
            jax.checkpoint(lambda row: row_loss(a, p, row, quant)),
            batch)) / n_targets

    return jax.value_and_grad(total)(params)


def adamw(params, grads, mu, nu, step, *, lr, b1, b2, eps, weight_decay):
    """Decoupled-weight-decay Adam (Loshchilov & Hutter), bias-corrected;
    ``step`` counts from 1."""
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def one(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / c1) / (jnp.sqrt(v / c2) + eps) + weight_decay * p
        return p - lr * upd, m, v

    out = jax.tree.map(one, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(  # noqa: E731
        lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


def leaf_norms(a, tree: dict) -> dict:
    """{leaf name: norm}: block leaves by their layer's absolute index
    (``"3/q"``), globals by name — the program's leaves, one for one."""
    out = {n: jnp.sqrt(jnp.sum(v * v)) for n, v in tree["g"].items()}
    for (_, first, _), run in zip(W.runs(a), tree["l"]):
        for n, v in run.items():
            per = jnp.sqrt(jnp.sum((v * v).reshape(v.shape[0], -1), -1))
            for i in range(v.shape[0]):
                out[f"{first + i}/{n}"] = per[i]
    return out


def train_reference(a, seed: int, job: dict, n_steps: int = 3, *,
                    quant: str = "", rows: int | None = None,
                    other_grads: dict | None = None,
                    keep_grads: bool = False) -> dict:
    """Follow the job's first ``n_steps`` steps. Returns the losses, the
    per-leaf norm of the first gradient, the parameters after the steps
    (on the device) and ``moved``: per element, whether the first
    gradient is at least a thousandth of the median leaf's root mean
    square — the others are nought to rounding (a key's bias in the
    dims that do not rotate) and move under Adam by round-off alone.
    ``rows`` < the job's batch is the planted fault "half of the batch
    left out, the mean taken over the rest". ``other_grads`` (a tree
    like the parameters: somebody else's first gradient) adds
    ``grad_diff_norms``, the per-leaf norm of its difference from this
    run's; ``keep_grads`` returns this run's first gradient on the host."""
    opt = job["optimizer"]
    hyp = dict(lr=opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
               eps=opt["eps"], weight_decay=opt["weight_decay"])
    data_key = W.root_key(seed, 1)
    b, s = job["global_batch"], job["seq_len"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                       static_argnums=(5,))
    def step(params, mu, nu, i, data_key, first, other=None):
        batch = W.token_batch(a, data_key, i, b, s)[:rows or b]
        loss, grads = loss_and_grads(a, params, batch, quant)
        extra = None
        if first:
            floor = 1e-3 * jnp.median(_leaf_rms(grads))
            extra = {"norms": leaf_norms(a, grads), "moved": jax.tree.map(
                lambda g: jnp.abs(g) >= floor, grads)}
            if other is not None:
                extra["diff"] = leaf_norms(
                    a, jax.tree.map(jnp.subtract, other, grads))
            if keep_grads:
                extra["grads"] = grads
        params, mu, nu = adamw(params, grads, mu, nu,
                               (i + 1).astype(jnp.float32), **hyp)
        return params, mu, nu, loss, extra

    params = init_train_params(a, seed)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    out: dict = {"losses": []}
    for i in range(n_steps):
        t0 = time.monotonic()
        params, mu, nu, loss, extra = step(
            params, mu, nu, jnp.int32(i), data_key, i == 0,
            other_grads if i == 0 else None)
        out["losses"].append(float(loss))
        out.setdefault("step_s", []).append(round(time.monotonic() - t0, 2))
        if i == 0:
            floats = lambda d: {k: float(v) for k, v in d.items()}  # noqa: E731
            out["grad_norms"] = floats(extra["norms"])
            out["moved"] = extra["moved"]
            if "diff" in extra:
                out["grad_diff_norms"] = floats(extra["diff"])
            if keep_grads:
                import numpy as np

                out["grads"] = jax.tree.map(np.asarray, extra["grads"])
            del extra
            other_grads = None
    del mu, nu
    out["params"] = params
    return out


def _leaf_rms(tree: dict):
    """Root mean square of every leaf (block leaves layer by layer)."""
    out = [jnp.sqrt(jnp.mean(v * v)) for v in tree["g"].values()]
    for run in tree["l"]:
        for v in run.values():
            out.extend(
                jnp.sqrt(jnp.mean((v * v).reshape(v.shape[0], -1), -1)))
    return jnp.stack(out)


def stacked(a, by_name: dict) -> dict:
    """``{"embed": x, "3/q": y, ...}`` -> the reference's tree."""
    import numpy as np

    return {"g": {n: by_name[n] for n, _, _ in W.global_leaves(a)},
            "l": [{n: np.stack([by_name[f"{i}/{n}"]
                                for i in range(first, stop)])
                   for n, _, _ in W.layer_leaves(a, first)}
                  for _, first, stop in W.runs(a)]}


def change_norms(a, seed: int, params: dict, moved: dict) -> dict:
    """Per-leaf norm of ``params`` minus the seed's initial weights over
    the elements that ``moved`` keeps; the initial weights are made
    again inside the one program that subtracts them."""
    return {k: float(v) for k, v in _change(
        a, params, moved, W.root_key(seed)).items()}


@functools.partial(jax.jit, static_argnums=(0,))
def _change(a, p, keep, key):
    return leaf_norms(a, jax.tree.map(
        lambda x, x0, k: jnp.where(k, x - x0, 0.0), p,
        _first_params(a, key), keep))
