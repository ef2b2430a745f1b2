"""A family whose layers are of several KINDS comes to the yardstick as
files: a throw-away family of two kinds (layer 0 ``wide``: ``mistral``'s
block with another ``ff`` and one more leaf; the rest ``plain``) is
written into a COPY of the tree, no file of the copy edited, and one
process started in the copy takes it through ``run.py --dry``'s
resolution, the weights, both halves of the general reference and the
counts. The tests below read what that process printed."""

import json
import math
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FAMILY = '''
import dataclasses

from benchmarks.families import mistral as M
from benchmarks.families.mistral import *  # noqa: F401,F403


@dataclasses.dataclass(frozen=True)
class Arch(M.Arch):
    wide_ff: int = 0


def arch(cfg):
    return Arch(**dataclasses.asdict(M.arch(cfg)),
                wide_ff=cfg["wide_intermediate_size"])


def layer_kind(a, layer):
    return "wide" if layer == 0 else "plain"


def layer_leaves(a, layer):
    assert type(layer) is int, "never a tracer"
    if layer_kind(a, layer) == "plain":
        return M.layer_leaves(a, layer)
    return M.layer_leaves(dataclasses.replace(a, ff=a.wide_ff), layer) \\
        + [("wo.bias", (a.d,), "b")]


def block(a, p, x, quant="", kind="plain"):
    y = M.block(a, p, x, quant)      # reads the widths off the leaves
    return y + p["wo.bias"] if kind == "wide" else y
'''

# one kind for every layer, and layer 0 lists a leaf the others do not
BAD_FAMILY = FAMILY.replace(
    'return "wide" if layer == 0 else "plain"', 'return "plain"').replace(
    'if layer_kind(a, layer) == "plain":', 'if layer > 0:')

DRIVE = '''
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import reference as R, weights as W, work as K

cfg = json.load(open("benchmarks/configs/twokinds-l3.json"))
a, seed, out = W.arch(cfg, rehearsal=True), 2**31 + 17, {}
F = W.family(a.family)
out["runs"] = W.runs(a)
out["leaves"] = [[n for n, _, _ in W.layer_leaves(a, i)]
                 for i in range(a.layers)]
out["counts"] = [K.params(a), K.matmul_params(a), K.n_params(a),
                 [K.layer_params(a, i) for i in range(a.layers)],
                 [K.layer_matmul_params(a, i) for i in range(a.layers)]]

# every layer's weights under a TRACED index against the whole model's
key = W.root_key(seed)
whole = jax.jit(lambda k: W.all_weights(a, k, jnp.bfloat16))(key)
traced = jax.jit(lambda k, i, kind: W.layer_weights(
    a, k, i, jnp.bfloat16, kind), static_argnums=2)
out["traced_equal"] = []
for i, lw in enumerate(whole["layers"]):
    got = traced(key, jnp.int32(i), W.layer_kind(a, i))
    out["traced_equal"].append(list(got) == list(lw) and all(
        got[n].shape == lw[n].shape and got[n].dtype == lw[n].dtype
        and bool((got[n] == lw[n]).all()) for n in lw))

# a hand-written loop over block, the serving walk, the training scan
toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0,
                                     a.vocab))
g = W.global_weights(a, seed, jnp.float32)
hand = []
for row in toks:
    x = g["embed"][row]
    for i in range(a.layers):
        x = F.block(a, W.layer_weights(a, seed, i, jnp.float32), x,
                    kind=W.layer_kind(a, i))
    hand.append(F.logits(a, g, x))
hand = np.asarray(jnp.stack(hand))                           # [2, 48, V]
pos = np.tile(np.arange(48, dtype=np.int32)[None], (2, 1))
target = (toks[:, ::-1] % a.vocab).astype(np.int32)
before = R._serve_layer._cache_size()
best, at, argmax = (np.asarray(v) for v in R.serve_logits(
    a, seed, toks, pos, target, dtype=jnp.float32))
out["serve_layer_programs"] = R._serve_layer._cache_size() - before
out["serve_gap"] = float(max(
    np.abs(best - hand.max(-1)).max(),
    np.abs(at - np.take_along_axis(hand, target[..., None], -1)[..., 0]).max()))
out["serve_argmax_equal"] = bool((argmax == hand.argmax(-1)).all())
params = R.init_train_params(a, seed)
scan = np.asarray(jnp.stack([F.logits(
    a, params["g"], R.row_hidden(a, params, jnp.asarray(row)))
    for row in toks]))
out["scan_gap"] = float(np.abs(scan - hand).max())
logp = jax.nn.log_softmax(jnp.asarray(hand[0, :-1]), -1)
out["loss"] = [float(R.row_loss(a, params, jnp.asarray(toks[0]))), float(
    -jnp.sum(jnp.take_along_axis(logp, toks[0, 1:, None], -1)))]

# the training tree: names by absolute layer, and back
names = [n for n, _, _ in W.global_leaves(a)] + [
    f"{i}/{n}" for i in range(a.layers) for n, _, _ in W.layer_leaves(a, i)]
out["names"] = sorted(names)
out["norm_keys"] = sorted(R.leaf_norms(a, params))
whole32 = jax.jit(lambda k: W.all_weights(a, k, jnp.float32))(key)
by_name = dict(whole32["g"]) | {f"{i}/{n}": v for i, lw in enumerate(
    whole32["layers"]) for n, v in lw.items()}
back = R.stacked(a, {n: np.asarray(v) for n, v in by_name.items()})
out["stacked_back"] = jax.tree.structure(back) == jax.tree.structure(params) \\
    and all(bool((x == np.asarray(y)).all()) for x, y in zip(
        jax.tree.leaves(back), jax.tree.leaves(params)))
norms = R.leaf_norms(a, params)
out["norms_are_the_leaves"] = all(
    abs(float(norms[n]) - float(jnp.sqrt(jnp.sum(v * v)))) < 1e-4
    for n, v in by_name.items())

# the training reference follows two steps of the job at toy size
job = json.load(open("benchmarks/traffic/pretrain-2k.json"))
job.update(job["rehearsal"])
ref = R.train_reference(a, seed, job, 2)
out["losses"] = ref["losses"]
out["grad_keys"] = sorted(ref["grad_norms"])
delta = R.change_norms(a, seed, ref.pop("params"), ref.pop("moved"))
out["delta"] = [sorted(delta) == out["names"], min(delta.values())]

# a family of one kind still compiles one layer program
m = W.arch(json.load(open("benchmarks/configs/mistral-7b-v0.3-l10.json")),
           rehearsal=True)
before = R._serve_layer._cache_size()
R.serve_logits(m, seed, toks % m.vocab, pos, target % m.vocab,
               dtype=jnp.float32)
out["one_kind"] = [W.runs(m), R._serve_layer._cache_size() - before]

try:
    W.arch(dict(cfg, model_type="badkinds"), rehearsal=True)
except ValueError as e:
    out["refused"] = str(e)
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The copied tree with the family, its configuration and its cell
    ADDED, and what ``--dry`` and the drive printed there."""
    tmp = tmp_path_factory.mktemp("kinds")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp / "benchmarks"
    (b / "families" / "twokinds.py").write_text(FAMILY)
    (b / "families" / "badkinds.py").write_text(BAD_FAMILY)
    cfg = json.load(open(b / "configs" / "mistral-7b-v0.3-l10.json"))
    cfg.update(name="twokinds-l3", model_type="twokinds", num_hidden_layers=3,
               wide_intermediate_size=28672)
    cfg["rehearsal"].update(num_hidden_layers=3, wide_intermediate_size=384)
    (b / "configs" / "twokinds-l3.json").write_text(json.dumps(cfg))
    shutil.copy(b / "limits" / "mistral-7b.chat-steady.json",
                b / "limits" / "twokinds.chat-steady.json")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append(dict(
        bench["configs"][0], name="twokinds-l3",
        file="benchmarks/configs/twokinds-l3.json"))
    bench["workloads"].append({
        "name": "twokinds.chat-steady", "config": "twokinds-l3",
        "traffic": "chat-open-steady", "chips": 1, "why": "a test"})
    bench["end_to_end"][0]["workloads"].append("twokinds.chat-steady")
    bench["per_layer"][0]["workloads"].append("twokinds.chat-steady")
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "drive.py").write_text(DRIVE)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    dry = subprocess.run(
        [sys.executable, str(b / "run.py"), "--dry", "--workload",
         "twokinds.chat-steady"], capture_output=True, text=True, env=env)
    assert dry.returncode == 0, dry.stderr
    drive = subprocess.run([sys.executable, "drive.py"], cwd=tmp, env=env,
                           capture_output=True, text=True, timeout=900)
    assert drive.returncode == 0, drive.stderr[-3000:]
    return {"dry": json.loads(dry.stdout),
            "drive": json.loads(drive.stdout.splitlines()[-1])}


def test_dry_resolves_the_family_and_counts_its_kinds(copy):
    assert copy["dry"]["family"] == "twokinds"
    assert copy["dry"]["layer_kinds"] == {"wide": 1, "plain": 2}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--dry",
         "--workload", "pythia-1.4b.pretrain-2k"], capture_output=True,
        text=True)
    assert json.loads(out.stdout)["layer_kinds"] == {"layer": 6}


def test_runs_are_the_consecutive_layers_of_one_kind(copy):
    d = copy["drive"]
    assert d["runs"] == [["wide", 0, 1], ["plain", 1, 3]]
    assert d["leaves"][0] == d["leaves"][1] + ["wo.bias"]
    assert d["leaves"][1] == d["leaves"][2]
    assert d["one_kind"][0] == [["layer", 0, 2]]


def test_counts_are_summed_over_the_layers_of_every_kind(copy):
    blocks, matmuls, n, per_layer, per_layer_mm = copy["drive"]["counts"]
    attn = 128 * (4 * 32) * 2 + 128 * (2 * 32) * 2
    plain, wide = attn + 3 * 128 * 256, attn + 3 * 128 * 384
    assert per_layer_mm == [wide, plain, plain] and matmuls == wide + 2 * plain
    assert per_layer == [wide + 2 * 128 + 128, plain + 2 * 128, plain + 2 * 128]
    assert blocks == sum(per_layer) and n == blocks + 2 * 512 * 128 + 128


def test_weights_under_a_traced_index_are_the_whole_models(copy):
    assert copy["drive"]["traced_equal"] == [True, True, True]


def test_walk_scan_and_a_hand_loop_give_the_same_logits(copy):
    d = copy["drive"]
    assert d["serve_gap"] < 1e-5 and d["serve_argmax_equal"]
    assert d["scan_gap"] < 1e-5
    assert d["loss"][0] == pytest.approx(d["loss"][1], rel=1e-5)


def test_the_training_tree_names_leaves_by_their_absolute_layer(copy):
    d = copy["drive"]
    assert "0/wo.bias" in d["names"] and "1/wo.bias" not in d["names"]
    assert "2/wg" in d["names"]
    assert d["norm_keys"] == d["names"] == d["grad_keys"]
    assert d["stacked_back"] and d["norms_are_the_leaves"]


def test_the_training_reference_follows_a_family_of_two_kinds(copy):
    d = copy["drive"]
    # random ids, weights of 0.02: each step's loss is about ln(vocab)
    assert len(d["losses"]) == 2
    assert all(abs(x - math.log(512)) < 0.1 for x in d["losses"])
    assert d["delta"][0] and d["delta"][1] > 0      # every leaf moved


def test_the_serving_walk_compiles_one_layer_program_a_kind(copy):
    assert copy["drive"]["serve_layer_programs"] == 2
    assert copy["drive"]["one_kind"][1] == 1


def test_a_kind_whose_layers_list_unequal_leaves_is_refused(copy):
    said = copy["drive"]["refused"]
    assert "layers 0 and 1" in said and "'plain'" in said
    assert "badkinds.py" in said
