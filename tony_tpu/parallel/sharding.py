"""Sharding presets: logical-axis rules -> PartitionSpecs for model states.

The framework's models annotate arrays with *logical* axis names
("batch", "seq", "embed", "heads", "kv_heads", "mlp", "vocab", "expert",
"layers"); "kv_heads" is the GQA-shrunk K/V head dim — always replicated,
since its size (n_kv_heads) is typically smaller than the tensor axis;
a preset maps logical names to mesh axes. This is the pjit idiom: the same
model runs DP, FSDP, TP, or combinations by swapping the rule set, and XLA
inserts the collectives (no NCCL-style explicit comms as in the reference's
delegated data plane, SURVEY.md section 2.5).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tony_tpu.parallel.mesh import DATA, EXPERT, FSDP, PIPE, SEQ, TENSOR

# logical axis -> mesh axis (or None = replicated) per strategy
RULES: dict[str, dict[str, Any]] = {
    # pure data parallelism: params replicated, batch sharded
    "dp": {
        "batch": (DATA, FSDP),
        "seq": None, "embed": None, "heads": None, "kv": None, "kv_heads": None,
        "mlp": None, "vocab": None, "expert": None, "layers": None,
    },
    # fsdp: params sharded on the fsdp axis along their largest dim
    "fsdp": {
        "batch": (DATA, FSDP),
        "embed": FSDP,
        "seq": None, "heads": None, "kv": None, "kv_heads": None, "mlp": None,
        "vocab": None, "expert": None, "layers": None,
    },
    # tensor parallelism (megatron-style): heads + mlp sharded
    "tp": {
        "batch": (DATA, FSDP),
        "heads": TENSOR, "mlp": TENSOR, "vocab": TENSOR,
        "seq": None, "embed": None, "kv": None, "kv_heads": None, "expert": None, "layers": None,
    },
    # fsdp + tp combined (the common large-model preset)
    "fsdp_tp": {
        "batch": (DATA, FSDP),
        "embed": FSDP, "heads": TENSOR, "mlp": TENSOR, "vocab": TENSOR,
        "seq": None, "kv": None, "kv_heads": None, "expert": None, "layers": None,
    },
    # sequence/context parallelism: activations sharded along seq
    "sp": {
        "batch": (DATA, FSDP),
        "act_seq": SEQ,
        "seq": None, "embed": None, "heads": None, "kv": None, "kv_heads": None,
        "mlp": None, "vocab": None, "expert": None, "layers": None,
    },
    # expert parallelism for MoE blocks
    "ep": {
        "batch": (DATA, FSDP),
        "expert": EXPERT,
        "seq": None, "embed": None, "heads": None, "kv": None, "kv_heads": None,
        "mlp": None, "vocab": None, "layers": None,
    },
    # expert + tensor combined (large MoE: experts over the expert axis,
    # each expert's ffn dim + attention heads over tensor, batch over data)
    "ep_tp": {
        "batch": (DATA, FSDP),
        "expert": EXPERT, "heads": TENSOR, "mlp": TENSOR, "vocab": TENSOR,
        "seq": None, "embed": None, "kv": None, "kv_heads": None,
        "layers": None,
    },
    # pipeline: layers sharded across stages (used with parallel.pipeline)
    "pp": {
        "batch": (DATA, FSDP),
        "layers": PIPE,
        "seq": None, "embed": None, "heads": None, "kv": None, "kv_heads": None,
        "mlp": None, "vocab": None, "expert": None,
    },
    # SERVING tensor/expert parallelism (the sharded-replica preset,
    # ISSUE-14). Differs from "tp" in three deliberate ways:
    #   - "kv_heads" CAN shard: the paged KV pools shard on the kv-head
    #     axis, so the K/V projections must produce kv-head-sharded
    #     outputs to write into them locally (``serve_spec_for``'s
    #     validation replicates any dim the tensor axis does not
    #     divide, so small-GQA models degrade to replicated pools
    #     instead of failing);
    #   - batch replicated: a serving replica's slots are its own, the
    #     mesh buys per-chip capacity, not batch splitting;
    #   - NO contraction dim is ever sharded: row-parallel kernels
    #     (attention o, MLP wo — anything whose logical axes end in
    #     "embed" with a tensor-sharded "heads"/"mlp" before it) FLIP
    #     to output-dim (embed) sharding. A Megatron-style row-parallel
    #     layout psums per-shard partial products — a different float
    #     reduction order than one chip, which would break the serving
    #     engine's token-exactness contract. Output-dim sharding keeps
    #     every arithmetic reduction whole on one chip (identical
    #     contraction extents, identical order); all cross-chip ICI
    #     traffic is all-gather — pure data movement, bitwise. That is
    #     the structural argument behind the mesh=1 vs mesh=N
    #     byte-identical-streams gate (tests/test_shard_serve.py) —
    #     a CPU-backend property: on v5e chips the streams diverged
    #     (PR 24), the logits agreeing to rounding.
    "serve": {
        "batch": None,
        "heads": TENSOR, "kv_heads": TENSOR, "mlp": TENSOR,
        "vocab": TENSOR, "expert": EXPERT,
        "seq": None, "embed": None, "kv": None, "layers": None,
    },
}

# logical names that mark a column-parallel kernel's OUTPUT-turned-
# contraction dim in the row-parallel sibling (o consumes heads, wo
# consumes mlp) — the serve preset flips these to embed-sharded
_SERVE_FLIP_AXES = ("heads", "kv_heads", "mlp")


def spec_for(logical_axes: tuple[str | None, ...], rules: dict[str, Any]) -> P:
    """PartitionSpec from per-dimension logical names."""
    parts = []
    for name in logical_axes:
        if name is None:
            parts.append(None)
        else:
            parts.append(rules.get(name))
    # trailing Nones can be dropped but keeping them is harmless
    return P(*parts)


def tree_shardings(mesh: Mesh, logical_tree: Any, preset: str) -> Any:
    """Map a pytree of logical-axis tuples to NamedShardings."""
    rules = RULES[preset]
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, spec_for(axes, rules)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def shard_params_by_size(mesh: Mesh, params: Any, axis: str = FSDP,
                         min_size: int = 2**14) -> Any:
    """Heuristic FSDP sharding for arbitrary param trees (when a model has
    no logical annotations): shard each large array along its largest
    dimension divisible by the axis size; replicate the rest."""
    n = mesh.shape.get(axis, 1)

    def spec(x):
        if n <= 1 or x.size < min_size:
            return NamedSharding(mesh, P())
        dims = sorted(range(x.ndim), key=lambda d: -x.shape[d])
        for d in dims:
            if x.shape[d] % n == 0:
                parts: list = [None] * x.ndim
                parts[d] = axis
                return NamedSharding(mesh, P(*parts))
        return NamedSharding(mesh, P())

    return jax.tree.map(spec, params)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Inputs: batch dim sharded over (data, fsdp)."""
    axes = tuple(a for a in (DATA, FSDP) if mesh.shape.get(a, 1) > 1)
    return NamedSharding(mesh, P(axes if axes else None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ------------------------------------------------- serving (ISSUE-14)


def _axis_size(mesh: Mesh, assignment) -> int:
    """Total shard count an axis assignment (name | tuple | None)
    splits a dim into."""
    if assignment is None:
        return 1
    if isinstance(assignment, tuple):
        n = 1
        for a in assignment:
            n *= mesh.shape.get(a, 1)
        return n
    return mesh.shape.get(assignment, 1)


def validated_spec(mesh: Mesh, spec: P, shape: tuple) -> P:
    """Drop per-dim assignments the dim size does not divide — the
    shape-safe fallback (a NamedSharding over a non-divisible dim
    fails at placement; replicating that dim is always correct)."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, assignment in zip(shape, parts):
        n = _axis_size(mesh, assignment)
        out.append(assignment if n > 1 and dim % n == 0 else None)
    return P(*out)


def serve_spec_for(logical_axes: tuple, rules: dict[str, Any]) -> P:
    """``spec_for`` plus the serve preset's row-parallel FLIP: a kernel
    whose logical axes END in "embed" with a tensor-sharded
    "heads"/"kv_heads"/"mlp" before it (attention o, MLP/MoE wo, and
    their int8 kernel_q8 twins) is the Megatron row-parallel layout —
    sharding that leading axis would shard the CONTRACTION and psum
    per-shard partials (a different float reduction order than one
    chip). Instead the sharding moves to the trailing embed (output)
    dim: each chip reads its kernel slice, contracts over the FULL
    gathered input, and produces exact output columns — all
    cross-chip traffic stays all-gather."""
    parts = [rules.get(name) if name is not None else None
             for name in logical_axes]
    if len(parts) >= 2 and logical_axes[-1] == "embed":
        flip = [i for i, name in enumerate(logical_axes[:-1])
                if name in _SERVE_FLIP_AXES and parts[i] == TENSOR]
        if flip:
            for i in flip:
                parts[i] = None
            parts[-1] = TENSOR
    return P(*parts)


def serving_shardings(mesh: Mesh, params: Any,
                      preset: str = "serve") -> Any:
    """NamedShardings for a transformer param tree under the serving
    preset: logical axes from the param path names
    (``models.transformer.logical_axis_rules_tree`` — int8 kernel_q8 /
    scale leaves shard alongside their bf16 twins), the serve rules'
    row-parallel flip, and per-dim divisibility validation (anything
    the mesh does not divide replicates — GQA kv heads smaller than
    the tensor axis, odd vocab sizes, adapter ranks)."""
    from tony_tpu.models.transformer import logical_axis_rules_tree

    rules = RULES[preset]
    logical = logical_axis_rules_tree(params)

    def spec(axes, leaf):
        p = serve_spec_for(axes, rules) if preset == "serve" \
            else spec_for(axes, rules)
        return NamedSharding(mesh, validated_spec(mesh, p, leaf.shape))

    return jax.tree.map(spec, logical, params,
                        is_leaf=lambda x: isinstance(x, tuple))


def _kv_leaf_head_axis(path, leaf) -> int | None:
    """kv-head axis of a serving-cache leaf, by the cache name
    contract (serve/slots.cache_batch_axis keys the same names for
    the page/batch axis): KV buffers are [..., pages|b, len, kvh, dh],
    scales [..., pages|b, len, kvh]. None = not a KV leaf (shared
    counters) — replicated."""
    name = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
    if name in ("cached_key", "cached_value"):
        return leaf.ndim - 2
    if name in ("cached_key_scale", "cached_value_scale"):
        return leaf.ndim - 1
    return None


def kv_cache_shardings(mesh: Mesh, cache: Any, axis: str = TENSOR) -> Any:
    """NamedShardings for a serving KV cache pytree (paged pools or
    fixed-shape rows): every KV leaf shards its KV-HEAD dim over
    ``axis`` — the page/batch and position dims stay whole, so the
    host-side page tables, free-list allocator, and reservation ledger
    are untouched (a page id means the same thing on every chip; only
    the page's CONTENT is split by head). Leaves whose kv-head count
    the axis does not divide replicate (small-GQA fallback), as do the
    shared position counters."""
    n = mesh.shape.get(axis, 1)

    def spec(path, leaf):
        ax = _kv_leaf_head_axis(path, leaf)
        if ax is None or n <= 1 or leaf.shape[ax] % n:
            return NamedSharding(mesh, P())
        parts: list = [None] * leaf.ndim
        parts[ax] = axis
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map_with_path(spec, cache)


def kv_shard_count(mesh: Mesh, cache: Any, axis: str = TENSOR) -> int:
    """How many ways ``kv_cache_shardings`` actually splits the KV
    pools (1 = replicated fallback) — the divisor per-chip KV byte
    pricing and the capacity math use."""
    n = mesh.shape.get(axis, 1)
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        ax = _kv_leaf_head_axis(path, leaf)
        if ax is not None:
            return n if n > 1 and leaf.shape[ax] % n == 0 else 1
    return 1


def tree_shard_bytes(tree: Any, shardings: Any) -> int:
    """PER-CHIP bytes of ``tree`` placed under ``shardings`` — each
    leaf contributes its shard's bytes (replicated leaves their whole
    size). The number the capacity-unlock math and the goodput
    ledger's per-chip dispatch pricing are built on."""
    leaves = jax.tree_util.tree_leaves(tree)
    shards = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    total = 0
    for leaf, sh in zip(leaves, shards):
        shape = sh.shard_shape(tuple(leaf.shape))
        total += int(np.prod(shape)) * leaf.dtype.itemsize
    return total


def tree_shard_count(tree: Any, shardings: Any) -> int:
    """PER-CHIP element count under ``shardings`` (the FLOPs twin of
    ``tree_shard_bytes`` — per-chip matmul FLOPs track the parameters
    resident on that chip)."""
    leaves = jax.tree_util.tree_leaves(tree)
    shards = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    return sum(int(np.prod(sh.shard_shape(tuple(leaf.shape))))
               for leaf, sh in zip(leaves, shards))
