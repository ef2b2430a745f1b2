#!/usr/bin/env python3
"""Does the system still start, compile and answer on the chip?

    python chip_smoke.py             # one TPU chip: serve, then train
    python chip_smoke.py --chips 4   # one four-chip host: the mesh paths only

The quickest proof that both hot paths run on a TPU through the entry
points a user calls. It measures nothing and claims no speed.

One process per chip. This parent NEVER imports jax: it runs each phase
as a child, one after another, each with its own time limit, and learns
the device from what the children print. A phase that fails, times out
or reports a platform other than ``tpu`` makes the script exit non-zero.
Every phase prints one JSON line of its own; the LAST line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}`` and nothing
else — or ``{"ok": false, ...}``. Without a TPU it fails; it does not
carry on on the CPU.

One chip (what the driver runs):

- ``serve``: one child holds the chip and runs the real serving stack —
  ``cli.gateway.build_parser()`` arguments, the fleet built by
  ``build_gateway()``, the event-driven edge on port 0, ``serve.Server``
  defaults (paged KV, prefix store, in-dispatch EOS), ``--dtype bf16`` —
  over the ~0.99B GQA decoder at full width and depth, its weights
  initialised on the device from ``--seed``. A client thread sends real
  ``POST /v1/generate`` requests; the child also checks, outside HTTP,
  that logits from prefill-then-decode through the KV cache agree with a
  plain full forward. Ends with a real SIGTERM drain, exit code 0.
- ``train``: ``python -m tony_tpu.cli.submit`` -> client -> coordinator
  -> agent -> ``examples/lm-pretrain/pretrain.py`` at the 386M flagship.
  Only that last process may open the chip: the phase watches the
  process tree's open device files while the job runs.

``--chips 4`` runs only what exists across chips, each against its
one-device twin in the same process: the 0.99B model served under
``--mesh 4`` (token streams, and parameters and KV pool spread over the
four devices), and flagship ``Trainer`` steps on ``data_parallel_mesh()``
(losses).

``--tiny`` is the rehearsal: the same phases at toy sizes on whatever
devices jax has (``JAX_PLATFORMS=cpu``; pallas in interpret mode; for
``--chips 4`` give the CPU four devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``). Its last line
says ``"ok": false, "rehearsal": true``: a rehearsal is never a result.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
TOTAL_BUDGET_S = 1140  # the whole script answers within 1200 s
PHASES = {  # chips -> [(phase, its time limit in seconds)]
    1: [("serve", 660), ("train", 480)],
    4: [("mesh_serve", 780), ("dp_train", 360)],
}

# The two models the repo's first records were about (ROADMAP S1's first
# cells), and their toy twins for
# the rehearsal. Prompt lengths span three prefill buckets.
SERVE_MODEL = dict(vocab_size=32768, d_model=2048, n_layers=20, n_heads=16,
                   n_kv_heads=8, d_ff=8192, max_seq_len=2048)
SERVE_LENGTHS = dict(short=120, mid=400, long=1400, shared=360,
                     new=(32, 64, 48, 128), check_len=96, check_decode=8)
TRAIN_MODEL = dict(vocab=32768, d_model=1024, n_layers=28, n_heads=8,
                   n_kv_heads=8, d_ff=4096, seq_len=2048, block_q=512,
                   block_k=1024, ce_chunk=2048, global_batch=4)
TINY_SERVE_MODEL = dict(vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                        n_kv_heads=4, d_ff=256, max_seq_len=256)
TINY_SERVE_LENGTHS = dict(short=20, mid=80, long=150, shared=70,
                          new=(4, 6, 5, 8), check_len=24, check_decode=4)
TINY_TRAIN_MODEL = dict(vocab=256, d_model=64, n_layers=2, n_heads=4,
                        n_kv_heads=4, d_ff=128, seq_len=128, block_q=64,
                        block_k=128, ce_chunk=128, global_batch=4)
TRAIN_STEPS = 5
DP_STEPS = 3
# Gateway flags of every serving phase. --stall-timeout: the documented
# operator rule (docs/SERVING.md) is to keep it above one step's worst
# dispatch, first compile included. On a v5e a cold 0.99B step compiles a
# prefill bucket and the decode chunk back to back, ~26 s each: the 30 s
# default read that as a wedged replica and answered the first request
# 503 (PERF.md, PR 24).
SERVE_FLAGS = ["--dtype", "bf16", "--serve-batch", "4",
               "--stall-timeout", "600"]


# ====================================================================
# the parent: stdlib only, never jax
# ====================================================================

def _descendants(root: int) -> list[int]:
    """Every live process below ``root`` (children first found by
    ppid), from /proc — sessions and process groups do not hide one."""
    ppid: dict[int, int] = {}
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                # "pid (comm) state ppid ...": comm may hold spaces
                ppid[int(name)] = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
    out, frontier = [], [root]
    while frontier:
        cur = frontier.pop()
        kids = [p for p, pp in ppid.items() if pp == cur]
        out.extend(kids)
        frontier.extend(kids)
    return out


def _kill_tree(proc: subprocess.Popen) -> None:
    """Stop a child and everything it started, whatever session each
    process put itself in."""
    for pid in _descendants(proc.pid) + [proc.pid]:
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


def run_phase(name: str, argv: list[str], timeout_s: float) -> dict:
    """Run one phase as a child; its record is the last JSON object with
    a ``phase`` key on its stdout. Everything else it prints is passed
    on. A time-out or a missing record is a failed phase."""
    t0 = time.monotonic()
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE, text=True,
                            start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
        timed_out = False
    except subprocess.TimeoutExpired:
        _kill_tree(proc)  # a phase leaves nothing behind
        out, timed_out = proc.communicate()[0], True
    record = None
    for line in out.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            doc = None
        if isinstance(doc, dict) and doc.get("phase") == name:
            record = doc
        else:
            print(line, flush=True)
    if record is None:
        record = {"phase": name, "ok": False,
                  "error": "the phase printed no record"}
    record["exit_code"] = None if timed_out else proc.returncode
    record["wall_s"] = round(time.monotonic() - t0, 1)
    if timed_out:
        record["ok"] = False
        record["error"] = f"timed out after {timeout_s:.0f} s"
    elif proc.returncode != 0:
        record["ok"] = False
        record.setdefault("error", f"exit code {proc.returncode}")
    return record


def run_phases(phases: list[tuple[str, list[str], float]], chips: int,
               rehearsal: bool = False, budget_s: float = TOTAL_BUDGET_S,
               ) -> int:
    """Run the phases in order and print the verdict as the last line.
    Returns the exit code: 0 only when every phase ran on ``chips`` TPU
    devices and passed — a rehearsal passes on whatever devices it had,
    and still never prints ``"ok": true``."""
    deadline = time.monotonic() + budget_s
    failed, device = [], None
    for name, argv, limit in phases:
        left = deadline - time.monotonic()
        if left <= 1:
            rec = {"phase": name, "ok": False, "error": "no time left"}
        else:
            rec = run_phase(name, argv, min(limit, left))
        if rec.get("ok") is True and not rehearsal:
            if rec.get("platform") != "tpu":
                rec["ok"] = False
                rec["error"] = f"ran on {rec.get('platform')!r}, not a TPU"
            elif rec.get("device_count") != chips:
                rec["ok"] = False
                rec["error"] = (f"saw {rec.get('device_count')} device(s), "
                                f"asked for {chips}")
        print(json.dumps(rec), flush=True)
        if rec.get("ok") is not True:
            failed.append(name)
        elif device is None:
            device = {"platform": rec.get("platform"),
                      "kind": rec.get("device_kind"),
                      "count": rec.get("device_count")}
    if rehearsal:
        print(json.dumps({"ok": False, "rehearsal": True,
                          "passed": not failed, "failed": failed,
                          "device": device}), flush=True)
    elif failed:
        print(json.dumps({"ok": False, "failed": failed}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 1 if failed else 0


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tiny", action="store_true",
                   help="rehearsal at toy sizes on any device; never ok")
    p.add_argument("--phase", help=argparse.SUPPRESS)  # the child's entry
    a = p.parse_args(argv)
    if a.phase:
        return _child(a)
    common = [sys.executable, os.path.abspath(__file__), "--chips",
              str(a.chips), "--seed", str(a.seed)] \
        + (["--tiny"] if a.tiny else [])
    return run_phases([(name, common + ["--phase", name], limit)
                       for name, limit in PHASES[a.chips]],
                      a.chips, rehearsal=a.tiny)


# ====================================================================
# the children: each is one process, and at most one of a phase's
# processes opens the chip
# ====================================================================

class _Failed(Exception):
    """A check of a phase did not hold."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise _Failed(what)


def _child(a) -> int:
    sys.path.insert(0, REPO)
    record = {"phase": a.phase, "ok": False}
    try:
        _PHASE_FNS[a.phase](a, record)  # fills the record as it goes
        record["ok"] = True
    except _Failed as e:
        record["error"] = f"check failed: {e}"
    except Exception as e:  # noqa: BLE001 — reported, and the exit code
        import traceback    # below makes it a failure of the script

        traceback.print_exc()
        record["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(record), flush=True)
    return 0 if record["ok"] else 3


def _devices(a, record: dict) -> list:
    """Ask jax for its devices — the one question that takes the chip —
    and refuse anything but a TPU unless this is the rehearsal."""
    import jax

    devices = jax.devices()
    record.update(platform=devices[0].platform,
                  device_kind=devices[0].device_kind,
                  device_count=len(devices))
    _check(a.tiny or devices[0].platform == "tpu",
           f"jax found no TPU (platform {devices[0].platform!r})")
    return devices


class _CompileMeter:
    """What jax compiled or loaded, from its own monitoring events: how
    many executables were asked of the persistent cache, how many it
    held, and the seconds spent compiling or loading."""

    def __init__(self):
        import jax.monitoring as m

        self.requests = self.hits = 0
        self.seconds = 0.0
        m.register_event_listener(self._on_event)
        m.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def report(self) -> dict:
        return {"compile_s": round(self.seconds, 1),
                "executables": self.requests, "cache_hits": self.hits,
                "compiled_anew": self.requests - self.hits}


def _seeded_lm(cfg_kw: dict, seed: int, dtype=None):
    """``Transformer`` + parameters initialised ON the device by one
    jitted program (and cast there) —
    the gateway CLI itself only loads a checkpoint directory."""
    import jax
    import jax.numpy as jnp

    from tony_tpu.models import Transformer, TransformerConfig

    model = Transformer(TransformerConfig(scan_layers=False, **cfg_kw))

    def init(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        if dtype is None:
            return params
        return jax.tree.map(
            lambda x: x.astype(dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)

    return model, jax.jit(init)(jax.random.PRNGKey(seed))


def _check_tokens(model, seed: int, n: int):
    import jax
    import jax.numpy as jnp

    return jax.random.randint(jax.random.PRNGKey(seed + 1), (2, n), 0,
                              model.cfg.vocab_size, jnp.int32)


def _cache_logits(model, params, tokens, n_prefill: int):
    """float32 logits [b, n, V] on the host for ``tokens`` fed through
    the KV cache: a prefill of ``n_prefill`` tokens, then one
    single-token decode step for each of the rest."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.models.generate import init_cache, single_decode_step

    @jax.jit
    def run(params, tokens):
        cache = init_cache(model, params, tokens.shape[0])
        logits, vars_ = model.apply(
            {"params": params, "cache": cache}, tokens[:, :n_prefill],
            decode=True, mutable=["cache"])

        def step(cache, tok):
            return single_decode_step(model, params, cache, tok)

        _, decoded = jax.lax.scan(step, vars_["cache"],
                                  tokens[:, n_prefill:].T)
        return jnp.concatenate([logits, jnp.moveaxis(decoded, 0, 1)], axis=1)

    return np.asarray(run(params, tokens).astype(jnp.float32))


def _logit_gap(got, ref, what: str) -> dict:
    """How far ``got`` logits are from ``ref``, checked against the one
    tolerance this script uses. Logits, not sampled tokens: with random
    weights an argmax flips on rounding (see ``argmax_agree``).

    Tolerance: both sides compute in bf16 (8 significand bits, unit
    roundoff 2^-8 = 0.0039) but in a different order — blockwise
    attention over the whole sequence against einsum attention over a
    cache, one-token matmuls against a batch of them, a matmul split
    over four chips against the whole one — so what may differ is
    rounding, accumulated over the depth. Allowed: 5% of the largest
    reference logit, a dozen roundoffs. A wrong position, a stale cache
    row, a dropped layer or a mis-ordered shard moves logits by their
    own size."""
    import numpy as np

    _check(got.shape == ref.shape, f"logit shapes {got.shape} {ref.shape}")
    _check(bool(np.isfinite(got).all() and np.isfinite(ref).all()),
           f"non-finite logits ({what})")
    scale, worst = float(np.abs(ref).max()), float(np.abs(got - ref).max())
    out = {"max_abs_diff": round(worst, 5), "max_abs_logit": round(scale, 4),
           "relative": round(worst / scale, 5), "allowed_relative": 0.05,
           "argmax_agree": round(float(np.mean(
               got.argmax(-1) == ref.argmax(-1))), 4)}
    _check(worst <= 0.05 * scale, f"{what}: {out}")
    return out


def _cache_vs_forward(model, params, seed: int, n_prefill: int,
                      n_decode: int) -> dict:
    """Prefill-then-decode through the KV cache against one plain
    forward of the same tokens with the same parameters."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tokens = _check_tokens(model, seed, n_prefill + n_decode)
    full = jax.jit(lambda p, t: model.apply({"params": p}, t))(
        params, tokens)
    return _logit_gap(_cache_logits(model, params, tokens, n_prefill),
                      np.asarray(full.astype(jnp.float32)),
                      "logits through the cache off the plain forward's")


# ------------------------------------------------------------- serve

def _http(url: str, doc: dict | None = None, timeout: float = 300.0):
    import urllib.request

    req = urllib.request.Request(
        url, data=None if doc is None else json.dumps(doc).encode())
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read().decode()


def _prompt(rng, n: int, vocab: int) -> list[int]:
    return rng.integers(1, vocab, size=n).tolist()


def _generate(url: str, prompt: list[int], n_new: int,
              stream: bool = False) -> list[int]:
    """One real ``POST /v1/generate``; returns the new tokens after
    checking the answer is a 200 of exactly ``n_new`` tokens (streamed:
    that the deltas add up to the final line too)."""
    status, body = _http(url + "/v1/generate", {
        "token_ids": prompt, "max_new_tokens": n_new, "stream": stream})
    _check(status == 200, f"status {status}: {body[:200]}")
    lines = [json.loads(ln) for ln in body.splitlines() if ln.strip()]
    final = lines[-1]
    new = final["token_ids"][len(prompt):]
    _check(final["token_ids"][:len(prompt)] == prompt and len(new) == n_new
           and final["metrics"]["tokens_out"] == n_new,
           f"asked {n_new} tokens, got {len(new)} ({final.get('finish_reason')})")
    if stream:
        deltas = [t for ln in lines[:-1] for t in ln["token_ids"]]
        _check(len(lines) > 1 and deltas == new,
               "streamed deltas do not add up to the final line")
    return new


def _serve_client(url: str, vocab: int, seed: int, sizes: dict,
                  meter: _CompileMeter, on_chip: bool) -> dict:
    """The traffic: a first wave that meets every shape once, then a
    second of the same shapes, during which nothing may compile."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n32, n64, n48, n128 = sizes["new"]
    tail = sizes["mid"] - sizes["shared"]

    def wave():
        """Six requests over three prefill buckets. The repeat and the
        shared prefix follow their original at once: the default 64 MB
        prefix store holds 12 pages of this model (768 tokens), so an
        entry does not outlive much traffic."""
        a, b, c = (_prompt(rng, sizes[k], vocab)
                   for k in ("short", "mid", "long"))
        _generate(url, a, n32)
        first = _generate(url, b, n64, stream=True)
        repeat = _generate(url, b, n64)                # exact repeat of b
        _check(repeat == first, "an exact repeat answered differently")
        _generate(url, b[:sizes["shared"]] + _prompt(rng, tail, vocab),
                  n128)                                # shares b's prefix
        _generate(url, c, n48)
        _generate(url, _prompt(rng, sizes["short"], vocab), n32)
        return 6

    n_req = wave()
    stats = json.loads(_http(url + "/stats")[1])
    before = stats["engine"]["dispatch"]
    compiled_before = meter.requests
    n_req += wave()  # the same shapes again, other tokens: steady state
    stats = json.loads(_http(url + "/stats")[1])
    after = stats["engine"]["dispatch"]
    _check(stats["completed"] == n_req and not stats["shed"],
           f"completed {stats['completed']} of {n_req}, shed {stats['shed']}")
    prefix = stats["engine"]["prefix"]
    _check(prefix["enabled"] and prefix["hits"] >= 4
           and prefix["hit_tokens"] >= 2 * (sizes["mid"] + sizes["shared"] // 2),
           f"no prefix hit on each repeat and each shared prefix: {prefix}")
    new_shapes = {k: after[k]["compiles"] - before.get(k, {}).get(
        "compiles", 0) for k in after}
    _check(not any(new_shapes.values()) and meter.requests == compiled_before,
           f"compiled in steady state: first-use dispatches {new_shapes}, "
           f"{meter.requests - compiled_before} executable(s) built")
    pages = stats["engine"]["kv_pages"]
    _check(pages["enabled"], "the paged KV cache is off")
    good = json.loads(_http(url + "/debug/goodput")[1])["replicas"][0]
    decode = good["utilization"]["decode"]
    if on_chip:
        # the roofline reference must come from the device's name, not
        # from a default: "TPU v5 lite" is in the table
        _check((good["hbm_gbps"] or 0) > 0 and decode["hbm_bw_pct"]
               is not None and decode["mfu_pct"] is not None,
               f"no HBM/FLOP peak resolved for this chip: {good['hbm_gbps']}"
               f" {decode}")
    return {"requests": n_req, "prefix": {k: prefix[k] for k in (
                "lookups", "hits", "hit_tokens", "prefill_tokens_saved")},
            "kv_pages": {k: pages[k] for k in ("total", "page_size")},
            "dispatches": {k: {"count": v["count"], "first_use": v["compiles"],
                               "first_use_ms": v["compile_ms"],
                               "steady_mean_ms": v["steady_mean_ms"]}
                           for k, v in after.items()},
            "hbm_gbps_reference": good["hbm_gbps"],
            "decode_hbm_bw_pct_estimate": decode["hbm_bw_pct"]}


def phase_serve(a, record: dict) -> None:
    import threading

    _devices(a, record)
    import jax
    import jax.numpy as jnp

    from tony_tpu.cli.gateway import build_parser, serve
    from tony_tpu.utils import compilecache

    meter = _CompileMeter()
    args = build_parser().parse_args(["--port", "0"] + SERVE_FLAGS)
    record["compile_cache"] = compilecache.enable(args.compile_cache)
    t0 = time.monotonic()
    cfg = TINY_SERVE_MODEL if a.tiny else SERVE_MODEL
    sizes = TINY_SERVE_LENGTHS if a.tiny else SERVE_LENGTHS
    model, params = _seeded_lm(cfg, a.seed, jnp.bfloat16)
    record["n_params"] = sum(x.size for x in jax.tree.leaves(params))
    record["logits_cache_vs_forward"] = _cache_vs_forward(
        model, params, a.seed, sizes["check_len"], sizes["check_decode"])

    outcome: dict = {}

    def on_ready(http):
        def run():
            try:
                outcome["served"] = _serve_client(
                    f"http://{http.host}:{http.port}", cfg["vocab_size"],
                    a.seed, sizes, meter, record["platform"] == "tpu")
            except BaseException as e:  # noqa: BLE001 — re-raised below
                outcome["error"] = e
            finally:  # a real SIGTERM: the gateway drains and returns
                os.kill(os.getpid(), signal.SIGTERM)

        threading.Thread(target=run, daemon=True).start()

    rc = serve(args, model, params, [], on_ready=on_ready)
    if "error" in outcome:
        raise outcome["error"]
    _check(rc == 0, f"the SIGTERM drain left exit code {rc}")
    record.update(outcome["served"], drain_exit_code=rc, **meter.report())
    record["run_s"] = round(time.monotonic() - t0 - meter.seconds, 1)


# ------------------------------------------------------------- train

def _pretrain_argv(m: dict, steps: int) -> str:
    return (f"--steps {steps} --global-batch {m['global_batch']} "
            f"--examples {m['global_batch']} --seq-len {m['seq_len']} "
            f"--vocab {m['vocab']} --d-model {m['d_model']} "
            f"--n-layers {m['n_layers']} --n-heads {m['n_heads']} "
            f"--n-kv-heads {m['n_kv_heads']} --d-ff {m['d_ff']} "
            f"--attention pallas --block-q {m['block_q']} "
            f"--block-k {m['block_k']} --remat-policy attn_saved "
            f"--fused-adamw --donate --lr 3e-4 --ce-chunk {m['ce_chunk']} "
            "--log-every 1")


_CHIP_FILES = ("/dev/accel", "/dev/vfio/")


def _chip_openers(root: int, seen: dict) -> None:
    """Note, for every process below ``root``, its command line and
    whether it holds the accelerator's device file open."""
    for pid in _descendants(root):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode().strip()
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue  # it exited between the listing and the read
        holds = False
        for fd in fds:
            try:
                holds |= os.readlink(f"/proc/{pid}/fd/{fd}").startswith(
                    _CHIP_FILES)
            except OSError:
                continue
        if cmd:
            seen[cmd] = seen.get(cmd, False) or holds


def _role(cmd: str) -> str:
    # in this order: the client's command line names the payload too
    for mark, role in (("tony_tpu.cli.submit", "client"),
                       ("tony_tpu.coordinator", "coordinator"),
                       ("tony_tpu.agent", "agent"),
                       ("pretrain.py", "payload")):
        if mark in cmd:
            return role
    return "other"


def phase_train(a, record: dict) -> None:
    """No jax here either: this process submits the job and watches."""
    import glob
    import shutil

    from tony_tpu.events.history import list_jobs
    from tony_tpu.utils import compilecache

    m = TINY_TRAIN_MODEL if a.tiny else TRAIN_MODEL
    work = tempfile.mkdtemp(prefix="chip_smoke_train_")
    cache_dir = compilecache.resolve_dir()
    cached_before = set(compilecache.entries(cache_dir))
    argv = [sys.executable, "-m", "tony_tpu.cli.submit",
            "--app_name", "chip-smoke-train", "--framework", "jax",
            "--executes", os.path.join(REPO, "examples", "lm-pretrain",
                                       "pretrain.py"),
            "--task_params", _pretrain_argv(m, TRAIN_STEPS),
            "--conf", "tony.application.launch-mode=local",
            "--conf", "tony.worker.instances=1",
            "--conf", "tony.worker.chips=1",
            "--conf", f"tony.staging-dir={work}/staging",
            "--conf", f"tony.history.location={work}/history",
            "--conf", "tony.client.poll-interval-ms=500"]
    if not a.tiny:
        # no hidden CPU fallback in the payload either: with the TPU
        # named alone, a jax that cannot open it fails at start-up
        argv += ["--shell_env", "JAX_PLATFORMS=tpu"]
    record["compile_cache"] = cache_dir
    t0 = time.monotonic()
    seen: dict = {}
    try:
        proc = subprocess.Popen(argv, cwd=REPO, env=dict(
            os.environ, PYTHONPATH=REPO + os.pathsep + os.environ.get(
                "PYTHONPATH", "")))
        while proc.poll() is None:
            _chip_openers(os.getpid(), seen)
            time.sleep(0.5)
        record["submit_exit_code"] = proc.returncode
        run_files = glob.glob(f"{work}/staging/*/metrics/run.json")
        logs = glob.glob(f"{work}/staging/*/logs/*")
        if proc.returncode != 0 or len(run_files) != 1:
            for path in logs:  # the only place the payload's error is
                with open(path, errors="replace") as f:
                    sys.stderr.write(f"--- {path}\n{f.read()[-6000:]}\n")
        _check(proc.returncode == 0, f"submit exited {proc.returncode}")
        _check(len(run_files) == 1, f"no metrics/run.json under {work}")
        with open(run_files[0]) as f:
            run = json.load(f)
        record.update(platform=run["platform"],
                      device_kind=run["device_kind"],
                      device_count=run["device_count"],
                      TPU_VISIBLE_DEVICES=run["tpu_visible_devices"])
        _check(a.tiny or run["platform"] == "tpu",
               f"the payload ran on {run['platform']!r}")
        losses = [h["loss"] for h in run["history"] if "loss" in h]
        record["losses"] = [round(x, 4) for x in losses]
        _check(run["steps_run"] == TRAIN_STEPS == len(losses),
               f"ran {run['steps_run']} steps, logged {len(losses)}")
        _check(all(map(math.isfinite, losses)),
               f"non-finite loss: {losses}")
        _check(losses[-1] < losses[0], f"the loss did not fall: {losses}")
        # a pallas call lowers to a tpu_custom_call; the interpreter to none
        record["mosaic_kernels_in_step"] = run["mosaic_kernels_in_step"]
        _check(a.tiny or run["mosaic_kernels_in_step"] > 0,
               "the train step holds no compiled pallas kernel")
        # fit() logs the cumulative rate: step k was dispatched k / rate_k
        # seconds in, so step 1 carries tracing and compiling
        rate = [h["steps_per_sec"] for h in run["history"]]
        record["compile_s"] = round(1 / rate[0], 1)
        record["run_s"] = round(len(rate) / rate[-1] - 1 / rate[0], 2)
        # the job as its user finds it afterwards: a final status and a
        # history record the portal can list
        jobs = list_jobs(f"{work}/history")
        _check(len(jobs) == 1 and jobs[0]["status"] == "SUCCEEDED",
               f"history record: {jobs}")
        record["history_record"] = os.path.basename(jobs[0]["jhist"])
        # one process per chip: only the user process opened the device
        holders = sorted({_role(c) for c, held in seen.items() if held})
        record["processes_seen"] = sorted({_role(c) for c in seen})
        record["chip_opened_by"] = holders
        _check(set(holders) <= {"payload"},
               f"the chip was opened by {holders}: "
               f"{[c for c, held in seen.items() if held]}")
        _check(a.tiny or holders == ["payload"],
               "no process was seen holding the chip's device file")
        new = set(compilecache.entries(cache_dir)) - cached_before
        record["compiled_anew"] = len(new)
    finally:
        record["wall_job_s"] = round(time.monotonic() - t0, 1)
        shutil.rmtree(work, ignore_errors=True)


# ------------------------------------------------------ four chips

def _greedy_streams(gateway, prompts: list, n_new: int) -> list:
    from tony_tpu.gateway import GenRequest

    tickets = [gateway.submit(GenRequest(list(p), max_new_tokens=n_new,
                                         id=f"r{i}"))
               for i, p in enumerate(prompts)]
    return [list(t.result(timeout=600).tokens) for t in tickets]


def _bytes_by_device(tree) -> dict:
    import jax

    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) \
                + shard.data.size * shard.data.dtype.itemsize
    return out


def phase_mesh_serve(a, record: dict) -> None:
    """The 0.99B model under ``--mesh 4`` against itself on device 0,
    both built by ``build_gateway`` in this one process."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    devices = _devices(a, record)
    _check(len(devices) >= 4, f"{len(devices)} device(s), need 4")
    from tony_tpu.cli.gateway import build_gateway, build_parser
    from tony_tpu.utils import compilecache

    meter = _CompileMeter()
    record["compile_cache"] = compilecache.enable()
    t0 = time.monotonic()
    cfg = TINY_SERVE_MODEL if a.tiny else SERVE_MODEL
    sizes = TINY_SERVE_LENGTHS if a.tiny else SERVE_LENGTHS
    model, params = _seeded_lm(cfg, a.seed, jnp.bfloat16)
    rng = np.random.default_rng(a.seed)
    prompts = [_prompt(rng, sizes[k], cfg["vocab_size"])
               for k in ("short", "mid", "long", "short")]
    n_new = sizes["new"][1]

    tokens = _check_tokens(model, a.seed, sizes["check_len"]
                           + sizes["check_decode"])

    def serve_with(extra):
        args = build_parser().parse_args(SERVE_FLAGS + extra)
        gateway = build_gateway(args, model, params, []).start()
        try:
            streams = _greedy_streams(gateway, prompts, n_new)
            # the engine's own model and placed parameters: under a mesh,
            # the sharded ones its dispatches run
            server = gateway.replicas[0].server
            logits = _cache_logits(server.model, server.params, tokens,
                                   sizes["check_len"])
            held = (_bytes_by_device(server.params),
                    _bytes_by_device(server.slots.cache),
                    server.kv_shards)
        finally:
            _check(gateway.drain(timeout=120), "drain timed out")
        return streams, logits, held

    sharded, l_mesh, (p_dev, kv_dev, kv_shards) = serve_with(["--mesh", "4"])
    mem = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
           for d in devices[:4]}
    gc.collect()
    single, l_one, (p_one, kv_one, _) = serve_with([])
    _check(all(len(s) == n_new for s in sharded + single),
           "a request came back short")
    total_p, total_kv = sum(p_one.values()), sum(kv_one.values())
    record.update(
        requests=len(prompts), new_tokens_each=n_new,
        param_bytes_by_device=p_dev, kv_pool_bytes_by_device=kv_dev,
        param_bytes_one_device=total_p, kv_pool_bytes_one_device=total_kv,
        kv_shards=kv_shards, bytes_in_use_by_device=mem)
    # a quarter each, give or take the leaves the preset replicates
    # (norm scales; nothing large): no device holds more than 30%
    _check(len(p_dev) == 4 and max(p_dev.values()) <= 0.30 * total_p,
           f"parameters not spread over four devices: {p_dev} of {total_p}")
    _check(kv_shards == 4 and len(kv_dev) == 4 and max(kv_dev.values())
           <= 0.30 * sum(kv_dev.values()),
           f"KV pool not spread over four devices: {kv_dev}")
    # PR 14's contract — --mesh N streams byte-identical to one chip —
    # holds on the CPU backend (tests/test_shard_serve.py) and did NOT on
    # v5e chips (PR 24: first differing tokens at 40, 32, 2, 2 of 64), so
    # token identity is reported, not required, and what is required is
    # that the sharded model's logits agree with the one-device model's.
    record["streams_identical"] = [s == t for s, t in zip(sharded, single)]
    record["first_differing_token"] = [
        next((i for i, (x, y) in enumerate(zip(s, t)) if x != y), None)
        for s, t in zip(sharded, single)]
    record["logits_mesh_vs_one_device"] = _logit_gap(
        l_mesh, l_one, "--mesh 4 logits off the one-device logits")
    record.update(meter.report())
    record["run_s"] = round(time.monotonic() - t0 - meter.seconds, 1)


def phase_dp_train(a, record: dict) -> None:
    """Flagship ``Trainer`` steps through ``fit()`` on
    ``data_parallel_mesh()`` over four devices, then the same global
    batches on one device: the losses must agree."""
    import dataclasses
    import gc

    import jax
    import jax.numpy as jnp

    devices = _devices(a, record)
    _check(len(devices) >= 4, f"{len(devices)} device(s), need 4")
    from tony_tpu.models import Transformer, TransformerConfig
    from tony_tpu.ops import chunked_cross_entropy
    from tony_tpu.parallel import data_parallel_mesh
    from tony_tpu.parallel.sharding import batch_sharding
    from tony_tpu.train import FusedAdamW, Trainer, fit
    from tony_tpu.utils import compilecache

    meter = _CompileMeter()
    record["compile_cache"] = compilecache.enable()
    t0 = time.monotonic()
    m = TINY_TRAIN_MODEL if a.tiny else TRAIN_MODEL
    cfg = TransformerConfig(
        vocab_size=m["vocab"], d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], n_layers=m["n_layers"], d_ff=m["d_ff"],
        max_seq_len=m["seq_len"], dtype=jnp.bfloat16,
        attention_backend="pallas", attention_block_size=m["block_q"],
        attention_block_k=m["block_k"], remat=True,
        remat_policy="attn_saved")
    params = jax.device_get(jax.jit(Transformer(cfg).init)(
        jax.random.PRNGKey(a.seed),
        jnp.zeros((1, m["seq_len"]), jnp.int32)))
    batches = [jax.device_get(jax.random.randint(
        jax.random.PRNGKey(a.seed + 1 + i), (m["global_batch"],
                                             m["seq_len"]),
        0, m["vocab"], jnp.int32)) for i in range(DP_STEPS)]

    def losses_on(devs):
        mesh = data_parallel_mesh(devices=devs)
        model = Transformer(dataclasses.replace(cfg, mesh=mesh))

        def apply_fn(p, batch):
            hidden = model.apply(p, batch["tokens"], return_hidden=True)
            return chunked_cross_entropy(
                hidden[:, :-1], p["params"]["embedding"],
                batch["tokens"][:, 1:], chunk_size=m["ce_chunk"],
                compute_dtype=jnp.bfloat16)

        trainer = Trainer(mesh=mesh, apply_fn=apply_fn,
                          optimizer=FusedAdamW(3e-4), donate=True,
                          compute_dtype=jnp.bfloat16)
        sharding = batch_sharding(mesh)
        result = fit(trainer, params,
                     ({"tokens": jax.device_put(b, sharding)}
                      for b in batches),
                     num_steps=DP_STEPS, log_every=1)
        spread = sorted({s.device.id for s in
                         result.state.step.addressable_shards})
        out = [h["loss"] for h in result.history if "loss" in h]
        del result
        gc.collect()
        return out, spread

    four, on_four = losses_on(devices[:4])
    one, on_one = losses_on(devices[:1])
    record.update(losses_four_devices=[round(x, 5) for x in four],
                  losses_one_device=[round(x, 5) for x in one],
                  state_on_devices=[on_four, on_one])
    _check(len(on_four) == 4 and len(on_one) == 1,
           f"state placed on {on_four} and {on_one}")
    _check(len(four) == len(one) == DP_STEPS, f"losses {four} vs {one}")
    _check(all(map(math.isfinite, four + one)),
           f"non-finite: {four} {one}")
    # Tolerance: the first loss is a forward of identical parameters —
    # only bf16 matmul tiling (1 row a device against 4) and the order
    # of the mean over the batch differ: 0.2%. Later losses follow
    # updates computed from bf16 gradients summed in a different order
    # across devices, so they drift apart slowly: 1%.
    rel = [abs(x - y) / abs(y) for x, y in zip(four, one)]
    record["relative_loss_diff"] = [round(r, 6) for r in rel]
    _check(rel[0] <= 2e-3 and max(rel) <= 1e-2,
           f"data-parallel losses off the one-device losses: {rel}")
    record.update(meter.report())
    record["run_s"] = round(time.monotonic() - t0 - meter.seconds, 1)


_PHASE_FNS = {"serve": phase_serve, "train": phase_train,
              "mesh_serve": phase_mesh_serve, "dp_train": phase_dp_train}

if __name__ == "__main__":
    sys.exit(main())
