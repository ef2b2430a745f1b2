"""Persistent XLA compilation-cache wiring (utils/compilecache.py).

One rule places the cache for every entry point: where
JAX_COMPILATION_CACHE_DIR is set, jax reads it and no code sets a
directory; where it is not, <checkout>/.jax_compile_cache. The e2e at
the end is the reason the cache exists: process 1 compiles cold and
populates the dir; process 2 — a genuinely separate interpreter —
compiles the same program and takes cache HITS (observed via jax's own
monitoring counter) while writing nothing new, under either half of
the rule.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from tony_tpu import constants as C
from tony_tpu.utils import compilecache

# Child body: enable the cache from env, count persistent-cache hits via
# jax's monitoring events (introspection only — the production path never
# touches jax internals), run one jitted program, report.
_CHILD = """
import json, sys
from tony_tpu.utils import compilecache
enabled = compilecache.enable()
hits = [0]
from jax._src import monitoring  # test-only hit counter
monitoring.register_event_listener(
    lambda name, **kw: hits.__setitem__(0, hits[0] + 1)
    if name == "/jax/compilation_cache/cache_hits" else None)
import jax, jax.numpy as jnp
out = jax.jit(lambda x: (x @ x + 1.0).sum())(jnp.ones((64, 64)))
out.block_until_ready()
print(json.dumps({"enabled": enabled, "hits": hits[0]}))
"""


def _run_child(extra_env: dict) -> dict:
    env = {**os.environ, **extra_env}
    out = subprocess.run([sys.executable, "-c", _CHILD],
                         capture_output=True, text=True, env=env,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def _reset(monkeypatch):
    monkeypatch.setattr(compilecache, "_enabled", None)


@pytest.fixture
def config_updates(monkeypatch):
    """Every ``jax.config.update`` the module makes, recorded and not
    applied (the test process keeps its own cache settings)."""
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    _reset(monkeypatch)
    monkeypatch.delenv(compilecache.JAX_ENV, raising=False)
    monkeypatch.delenv(C.COMPILE_CACHE_DIR, raising=False)
    monkeypatch.delenv(C.JOB_DIR, raising=False)
    return calls


def test_env_var_set_means_no_directory_is_set_in_code(
        tmp_path, monkeypatch, config_updates):
    """JAX_COMPILATION_CACHE_DIR placed from outside: jax's own reading
    of it stands. enable() reports it, may set thresholds, and neither
    sets the directory nor creates one — whatever else it is given."""
    placed = tmp_path / "placed-from-outside"
    monkeypatch.setenv(compilecache.JAX_ENV, str(placed))
    monkeypatch.setenv(C.COMPILE_CACHE_DIR, str(tmp_path / "shell-env"))
    assert compilecache.enable(str(tmp_path / "explicit")) == str(placed)
    assert "jax_compilation_cache_dir" not in dict(config_updates)
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) \
        in config_updates
    assert not placed.exists() and not (tmp_path / "explicit").exists()


def test_env_var_unset_means_the_fixed_in_checkout_path(
        monkeypatch, config_updates):
    """No variable, no argument: <checkout>/.jax_compile_cache — derived
    from where the code is, never from a temp name, a pid, a job id or
    the time (the path is part of the cache key). A job dir in the env
    changes nothing: the coordinator no longer scopes the cache to it."""
    monkeypatch.setenv(C.JOB_DIR, "/some/job/dir")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    expect = os.path.join(repo, ".jax_compile_cache")
    assert compilecache.enable() == expect == compilecache.DEFAULT_DIR
    assert dict(config_updates)["jax_compilation_cache_dir"] == expect
    assert os.path.isdir(expect)


def test_two_calls_and_two_processes_agree_on_the_path(
        monkeypatch, config_updates):
    first = compilecache.enable()
    assert compilecache.enable("/somewhere/else") == first  # sticky
    _reset(monkeypatch)
    assert compilecache.enable() == first  # a fresh resolution: same path
    # what a launcher that must stay off jax computes for its children
    assert compilecache.resolve_dir() == first


def test_explicit_directory_stands_in_for_the_checkout_default(
        tmp_path, monkeypatch, config_updates):
    """A CLI's --compile-cache DIR, or TONY_COMPILE_CACHE_DIR exported
    through tony.application.shell-env (the only way a job-scoped cache
    still comes about): argument beats env; the dir is created."""
    monkeypatch.setenv(C.COMPILE_CACHE_DIR, str(tmp_path / "shell-env"))
    got = compilecache.enable(str(tmp_path / "explicit"))
    assert got == str(tmp_path / "explicit") and os.path.isdir(got)
    _reset(monkeypatch)
    assert compilecache.enable() == str(tmp_path / "shell-env")
    assert ("jax_compilation_cache_dir", str(tmp_path / "explicit")) \
        in config_updates


def test_unwritable_directory_runs_cold(tmp_path, monkeypatch,
                                        config_updates):
    blocker = tmp_path / "a-file"
    blocker.write_text("")
    assert compilecache.enable(str(blocker / "cache")) is None
    assert "jax_compilation_cache_dir" not in dict(config_updates)


@pytest.mark.parametrize("cli", ["gateway", "replica", "generate"])
def test_cli_default_defers_to_the_rule(cli):
    """The three CLIs no longer carry a default of their own
    (~/.cache/...): unset means "the one rule", '' still disables."""
    import importlib

    parser = importlib.import_module(f"tony_tpu.cli.{cli}").build_parser()
    assert parser.get_default("compile_cache") is None


@pytest.mark.parametrize("var", [compilecache.JAX_ENV,
                                 C.COMPILE_CACHE_DIR])
def test_second_cold_process_reuses_cache(tmp_path, var):
    """The headline contract: a brand-new interpreter compiling the same
    program takes persistent-cache hits and adds no new entries —
    whether the directory was placed by jax's own variable or asked for
    explicitly."""
    cache = tmp_path / "cc"
    env = {compilecache.JAX_ENV: "", C.COMPILE_CACHE_DIR: "",
           var: str(cache)}

    first = _run_child(env)
    assert first["enabled"] == str(cache)
    assert first["hits"] == 0  # cold: nothing to hit
    populated = compilecache.entries(str(cache))
    assert populated  # cold run wrote executables

    second = _run_child(env)
    assert second["enabled"] == str(cache)
    assert second["hits"] > 0  # warm: reused at least the jitted program
    assert compilecache.entries(str(cache)) == populated  # nothing new
