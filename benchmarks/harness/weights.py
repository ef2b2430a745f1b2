"""Sizes and seeded weights: the benchmark's own, shared by the program's
adapter and the plain reference. Nothing here comes from ``tony_tpu``.

A configuration file carries the published ``config.json`` keys of its
model. ``arch`` hands them to the model family's file
(``benchmarks/families/<model_type>.py``), which turns them into its own
sizes and lists its leaves. ``leaf`` makes ONE weight from ``(seed, layer,
name)`` with ``jax.random``: each value is a function of the key and the
element's index alone, so a leaf made alone (the reference, layer by
layer) is bit-identical to the same leaf made inside the one jitted
program that builds the whole model for the system under test.

A family's layers may be of several KINDS (its ``layer_kind``; README,
"The contract of a family file"): the layers of one kind list the same
leaves, two kinds may not. ``runs`` lists the consecutive layers of one
kind, which is what the reference walks and scans; a leaf's key is
folded from its layer and its place in ITS kind's list, so the layer may
be traced where the kind is static.

Matrices are N(0, 0.02); norm scales 1 + 0.1 N; biases 0.02 N — scales
and biases are random on purpose, so a dropped bias or scale shows in
the comparison that decides ``correct``.
"""

from __future__ import annotations

import importlib


def family(model_type: str):
    """The module that holds everything the benchmark knows of one model
    family, ``benchmarks/families/<model_type>.py`` (README, "Adding
    things"). Takes a configuration's ``model_type`` or an ``Arch``'s
    ``family``."""
    name = "benchmarks.families." + model_type
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise SystemExit(
            f"benchmarks/families/{model_type}.py: no such file (model_type "
            f"{model_type!r} is a family the benchmark does not have yet)"
        ) from None


def arch(cfg: dict, rehearsal: bool = False):
    """The sizes of a configuration file (its ``rehearsal`` block laid
    over them for the CPU rehearsal), as its family's frozen dataclass.
    The general files read ``family``, ``d``, ``layers``, ``vocab`` and
    ``max_len`` from it and nothing else. Refused here, once: a kind
    whose layers do not all list the same leaves."""
    c = dict(cfg)
    if rehearsal:
        c.update(cfg["rehearsal"])
    a = family(c["model_type"]).arch(c)
    first: dict = {}
    for i in range(a.layers):
        kind, leaves = layer_kind(a, i), layer_leaves(a, i)
        j, like = first.setdefault(kind, (i, leaves))
        if leaves != like:
            raise ValueError(
                f"benchmarks/families/{a.family}.py: layers {j} and {i} are "
                f"both of kind {kind!r} and list different leaves; layers "
                "whose leaves differ are of different kinds")
    return a


def layer_leaves(a, layer: int = 0) -> list[tuple[str, tuple, str]]:
    """(name, shape, kind) of one block's weights, in a fixed order;
    kind: "w" matrix, "s" norm scale, "b" bias."""
    return family(a.family).layer_leaves(a, layer)


ONE_KIND = "layer"   # of every layer of a family that names no kinds


def layer_kind(a, layer: int) -> str:
    """What the general code keys a layer's leaf list and mathematics on."""
    named = getattr(family(a.family), "layer_kind", None)
    return named(a, layer) if named else ONE_KIND


def runs(a) -> list[tuple[str, int, int]]:
    """``(kind, first, stop)`` of the consecutive layers of one kind, in
    order: one run for a family of one kind, three for dense, routed,
    dense."""
    out: list[tuple[str, int, int]] = []
    for i in range(a.layers):
        kind = layer_kind(a, i)
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], i + 1)
        else:
            out.append((kind, i, i + 1))
    return out


def global_leaves(a) -> list[tuple[str, tuple, str]]:
    return family(a.family).global_leaves(a)


def root_key(seed: int, stream: int = 0):
    """``--seed`` may pass 2**31: split it, fold the halves."""
    import jax

    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                             seed >> 31)
    return jax.random.fold_in(key, stream)


def _leaves_at(a, layer, kind) -> list[tuple[str, tuple, str]]:
    """The leaf list a weight of ``layer`` is made from: the globals' at
    -1, the layer's own, or, where the index is traced, that of the
    first layer of its (static) ``kind``."""
    if isinstance(layer, int):
        return global_leaves(a) if layer < 0 else layer_leaves(a, layer)
    like = [first for k, first, _ in runs(a) if k == kind]
    if not like:
        raise ValueError(f"a traced layer needs its kind, not {kind!r}")
    return layer_leaves(a, like[0])


def leaf(a, seed, layer, name: str, dtype, kind: str | None = None):
    """One weight. ``layer`` is the block's index, or -1 for the
    embedding, the head and the final norm; it may be traced, and then
    ``kind`` says which kind of layer it is. ``seed`` is an int or a key
    from ``root_key``."""
    import jax
    import jax.numpy as jnp

    idx, (_, shape, init) = next(
        (i, s) for i, s in enumerate(_leaves_at(a, layer, kind))
        if s[0] == name)
    key = seed if hasattr(seed, "dtype") else root_key(seed)
    key = jax.random.fold_in(jax.random.fold_in(key, layer + 1), idx)
    x = jax.random.normal(key, shape, jnp.float32)
    x = {"w": 0.02 * x, "s": 1.0 + 0.1 * x, "b": 0.02 * x}[init]
    return x.astype(dtype)


def layer_weights(a, seed, layer, dtype, kind: str | None = None) -> dict:
    return {n: leaf(a, seed, layer, n, dtype, kind)
            for n, _, _ in _leaves_at(a, layer, kind)}


def global_weights(a, seed, dtype) -> dict:
    return {n: leaf(a, seed, -1, n, dtype) for n, _, _ in global_leaves(a)}


def all_weights(a, seed, dtype) -> dict:
    """Every weight, ``{"g": {...}, "layers": [{...}, ...]}`` — call it
    under ONE ``jax.jit`` so the model is made on the device in one
    program."""
    key = seed if hasattr(seed, "dtype") else root_key(seed)
    return {"g": global_weights(a, key, dtype),
            "layers": [layer_weights(a, key, i, dtype)
                       for i in range(a.layers)]}


def token_batch(a, seed, step, rows: int, seq: int):
    """Training batch ``step``: ``rows`` x ``seq`` ids, every row
    different, every step different."""
    import jax
    import jax.numpy as jnp

    key = seed if hasattr(seed, "dtype") else root_key(seed, 1)
    return jax.random.randint(jax.random.fold_in(key, step), (rows, seq), 0,
                              a.vocab, jnp.int32)
