"""Where the benchmark's seeded weights meet the system under test. How
the program names things — its config for an ``Arch``, the parameter tree
its model expects — is the family's to say (``program_config`` and
``program_tree`` of ``benchmarks/families/<model_type>.py``, the only two
functions of the benchmark that import ``tony_tpu``'s model code); this
module fills that tree from the seed. Only the children that hold the
chip import it."""

from __future__ import annotations

from . import weights as W


def program_config(a, dtype, **extra):
    return W.family(a.family).program_config(a, dtype, **extra)


def program_tree(a, w: dict) -> dict:
    return W.family(a.family).program_tree(a, w)


def leaf_names(a) -> dict:
    """The program's tree with, at each leaf, the reference's name for
    it (``"embed"``, ``"3/q.bias"``)."""
    return program_tree(a, {
        "g": {n: n for n, _, _ in W.global_leaves(a)},
        "layers": [{n: f"{i}/{n}" for n, _, _ in W.layer_leaves(a, i)}
                   for i in range(a.layers)]})


def seeded_params(a, seed: int, dtype):
    """The model's parameters, made on the device from the seed by one
    jitted program, in the dtype they are used in."""
    import jax

    key = W.root_key(seed)
    return jax.jit(lambda k: program_tree(a, W.all_weights(a, k, dtype)))(key)
