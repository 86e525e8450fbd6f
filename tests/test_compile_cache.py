"""Where the persistent XLA compile cache lives (utils/compile_cache.py).

One rule: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(the program then sets no directory in code), else the fixed
``<repo>/.xla_cache`` — never a path with a temp dir, uid, pid or time
in it, because the path is part of the cache key.

jax's config and the module's idempotence flag are process-global, so
the behavioural cases run in child processes.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

CHILD = """
import json, os, sys
import jax
updates = []
real_update = jax.config.update
def recording_update(name, value):
    updates.append(name)
    real_update(name, value)
jax.config.update = recording_update
from fasttalk_tpu.utils import compile_cache
got = compile_cache.enable_compilation_cache("")
import jax.numpy as jnp
jax.jit(lambda x: x * {salt} + 1)(jnp.ones((4,))).block_until_ready()
print(json.dumps({{"returned": got, "updates": updates,
                  "jax_dir": jax.config.jax_compilation_cache_dir,
                  "pid": os.getpid()}}))
"""


def _run_child(env_extra: dict, salt: int) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    p = subprocess.run([sys.executable, "-c", CHILD.format(salt=salt)],
                       env=env, cwd=REPO, text=True, capture_output=True,
                       timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_no_directory_is_set_in_code(tmp_path):
    target = tmp_path / "placed" / "cache"
    repo_cache = REPO / ".xla_cache"
    before = set(os.listdir(repo_cache)) if repo_cache.is_dir() else set()
    out = _run_child({"JAX_COMPILATION_CACHE_DIR": str(target)}, salt=7919)
    assert out["returned"] == str(target) == out["jax_dir"]
    assert "jax_compilation_cache_dir" not in out["updates"]
    # The two persistence thresholds are still set.
    assert "jax_persistent_cache_min_compile_time_secs" in out["updates"]
    assert "jax_persistent_cache_min_entry_size_bytes" in out["updates"]
    assert any(target.iterdir()), "no entries in the env's directory"
    after = set(os.listdir(repo_cache)) if repo_cache.is_dir() else set()
    assert after == before, "entries leaked into <repo>/.xla_cache"


def test_unset_env_gives_one_fixed_repo_path_across_processes(tmp_path):
    outs = []
    for i in range(2):
        tmp = tmp_path / f"tmp{i}"
        tmp.mkdir()
        outs.append(_run_child({"TMPDIR": str(tmp)}, salt=104729 + i))
    assert outs[0]["pid"] != outs[1]["pid"]
    for out in outs:
        assert out["returned"] == out["jax_dir"] == str(REPO / ".xla_cache")
        assert out["updates"].count("jax_compilation_cache_dir") == 1


def test_off_setting_disables(monkeypatch):
    from fasttalk_tpu.utils import compile_cache

    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    for setting in ("off", "0", "none", "FALSE"):
        assert compile_cache.enable_compilation_cache(setting) is None
    assert compile_cache._enabled_dir is None


def test_uncreatable_directory_raises(monkeypatch, tmp_path):
    """A start that cannot have its cache fails; it does not warn and
    recompile everything on every restart."""
    from fasttalk_tpu.utils import compile_cache

    blocker = tmp_path / "a_file"
    blocker.write_text("")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    monkeypatch.setattr(compile_cache, "REPO_CACHE_DIR",
                        str(blocker / ".xla_cache"))
    with pytest.raises(OSError):
        compile_cache.enable_compilation_cache("")
    assert compile_cache._enabled_dir is None


def test_config_rejects_the_path_form(monkeypatch):
    from fasttalk_tpu.utils.config import Config

    monkeypatch.setenv("TPU_COMPILE_CACHE", "/var/cache/xla")
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        Config()
    monkeypatch.setenv("TPU_COMPILE_CACHE", "off")
    assert Config().compile_cache == "off"


def test_exactly_one_place_sets_the_directory():
    """The tree has one ``jax_compilation_cache_dir`` update, guarded by
    the env variable being unset, and nothing process- or user-specific
    in the module that owns the path."""
    hits = []
    files = [*REPO.glob("*.py"), *(REPO / "fasttalk_tpu").rglob("*.py"),
             *(REPO / "scripts").glob("*.py")]
    for f in files:
        text = f.read_text()
        for m in re.finditer(r"update\(\s*[\"']jax_compilation_cache_dir",
                             text):
            hits.append((f.relative_to(REPO).as_posix(), m.start()))
    assert [h[0] for h in hits] == ["fasttalk_tpu/utils/compile_cache.py"]
    src = (REPO / "fasttalk_tpu/utils/compile_cache.py").read_text()
    guard = src.index("if not os.environ.get(ENV_DIR):")
    assert guard < hits[0][1] < guard + 200
    for banned in ("tempfile", "getuid", "getpid", "time."):
        assert banned not in src, banned
