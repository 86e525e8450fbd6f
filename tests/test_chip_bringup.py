"""Start-up rules that keep a run honest about the device it ran on.

- chip_smoke.py without a chip (or without the program) fails fast,
  prints no result and binds no port;
- bench.py's orchestrating parents never import jax: a chip belongs to
  one process, and the parent's children need it;
- Pallas kernels interpret on the cpu backend only;
- flags the engine cannot honour on a mesh are construction errors;
- the per-shape Pallas/XLA choice of a quantized matmul is recorded;
- on a TPU a missing HBM limit is an error, not a skipped check.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------- smoke

def test_chip_smoke_without_a_chip_fails_fast_and_binds_nothing():
    code = ("import socket, sys\n"
            "def no_bind(self, *a, **k):\n"
            "    raise AssertionError('chip_smoke bound a port')\n"
            "socket.socket.bind = no_bind\n"
            "import chip_smoke\n"
            "sys.exit(chip_smoke.main([]))\n")
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, text=True,
                       capture_output=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode not in (0, 3), p.stdout[-500:]
    assert time.monotonic() - t0 < 60
    assert "no accelerator" in p.stderr and "'cpu'" in p.stderr
    assert '"ok"' not in p.stdout
    assert "bound a port" not in p.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       text=True, capture_output=True, timeout=60)
    assert p.returncode not in (0, 3)
    assert "main.py" in p.stderr
    assert '"ok"' not in p.stdout


# ---------------------------------------------------------------- bench

def test_bench_radix_parent_runs_with_jax_unimportable():
    """The BENCH_MODE=radix parent orchestrates two children that need
    the chip. With jax (and Config) unimportable in the parent and the
    children stubbed, it must still reach its JSON line."""
    code = '''
import json, subprocess, sys

class Block:
    def find_spec(self, name, path=None, target=None):
        if name == "jax" or name.startswith("jax."):
            raise ImportError("jax is off limits in an orchestrating parent")
sys.meta_path.insert(0, Block())

def phase(ttft, radix):
    return {"followup_turns": 4,
            "followup_ttft_ms": {"p50": ttft, "p95": ttft * 2},
            "radix": radix,
            "device": {"platform": "tpu", "kind": "stub", "count": 1}}

def fake_run(argv, env=None, **kw):
    on = env["BENCH_RX_PHASE"] == "on"
    out = phase(10.0, {"hit_rate": 0.5, "hit_tokens": 64,
                       "bytes_saved": 4096}) if on else phase(30.0, {})
    return subprocess.CompletedProcess(argv, 0, stdout=json.dumps(out))
subprocess.run = fake_run

import bench
bench.main()
assert "jax" not in sys.modules, "bench parent imported jax"
assert "fasttalk_tpu.utils.config" not in sys.modules, \\
    "bench parent built a Config"
'''
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, text=True,
                       capture_output=True, timeout=60,
                       env={**os.environ, "BENCH_MODE": "radix"})
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["unit"] == "ms" and line["value"] == 10.0
    assert line["radix"]["on"]["device"]["platform"] == "tpu"


@pytest.mark.parametrize("mode", ["multiturn", "longctx", "int4", "paged",
                                  "roofline", "fleet", "disagg", "chaos"])
def test_bench_parent_imports_no_jax_before_first_child(mode):
    """Every other orchestrating mode: by the time the first child is
    spawned the parent has imported neither jax nor Config."""
    code = '''
import subprocess, sys

def fake_run(argv, env=None, **kw):
    assert "jax" not in sys.modules, "parent imported jax"
    assert "fasttalk_tpu.utils.config" not in sys.modules
    print("FIRST_CHILD_REACHED", flush=True)
    sys.exit(0)
subprocess.run = fake_run

import bench
bench.main()
'''
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, text=True,
                       capture_output=True, timeout=60,
                       env={**os.environ, "BENCH_MODE": mode})
    assert p.returncode == 0, p.stderr[-2000:]
    assert "FIRST_CHILD_REACHED" in p.stdout


# ------------------------------------------------------- Pallas backend

def test_pallas_interprets_on_cpu_only(monkeypatch):
    from fasttalk_tpu.ops.pallas_backend import (PallasBackendError,
                                                 resolve_interpret)

    assert resolve_interpret() is True          # conftest: cpu backend
    assert resolve_interpret(True) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    with pytest.raises(PallasBackendError, match="CPU test mode"):
        resolve_interpret(True)
    # A plug-in platform that is not named tpu must not interpret the
    # kernel on the device and call it a kernel run.
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    for arg in (None, True, False):
        with pytest.raises(PallasBackendError, match="'gpu'"):
            resolve_interpret(arg)


def test_kernel_entry_points_refuse_other_backends(monkeypatch):
    from fasttalk_tpu.ops.pallas_attention import decode_attend
    from fasttalk_tpu.ops.pallas_backend import PallasBackendError
    from fasttalk_tpu.ops.pallas_int8 import int8_matmul

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(PallasBackendError):
        int8_matmul(jnp.ones((2, 128)), jnp.ones((128, 128), jnp.int8),
                    jnp.ones((128,)))
    with pytest.raises(PallasBackendError):
        decode_attend(jnp.ones((1, 4, 32)), jnp.ones((1, 128, 2, 32)),
                      jnp.ones((1, 128, 2, 32)),
                      jnp.array([5], jnp.int32))


# ------------------------------------------------------ flags on a mesh

def test_config_rejects_pallas_flags_on_a_mesh():
    from fasttalk_tpu.utils.config import Config

    for kw, env in ((dict(use_pallas_attention=True),
                     "TPU_USE_PALLAS_ATTENTION"),
                    (dict(use_pallas_int8=True), "TPU_USE_PALLAS_INT8")):
        with pytest.raises(ValueError, match=f"{env}=true is single-"):
            Config(tp_size=2, **kw)
    # Unset: resolved from what the config can see, never an error.
    assert Config(tp_size=2).use_pallas_int8 is False
    assert Config().use_pallas_int8 is True
    assert Config(tp_size=2, use_pallas_int8=False).use_pallas_int8 is False


def test_engine_rejects_pallas_flags_on_a_mesh():
    from fasttalk_tpu.engine.engine import TPUEngine
    from fasttalk_tpu.engine.tokenizer import ByteTokenizer
    from fasttalk_tpu.models.configs import get_model_config
    from fasttalk_tpu.parallel.mesh import make_mesh

    cfg = get_model_config("test-tiny")
    mesh = make_mesh(tp=2)
    for kw, env in ((dict(use_pallas_attention=True),
                     "TPU_USE_PALLAS_ATTENTION"),
                    (dict(use_pallas_int8=True), "TPU_USE_PALLAS_INT8")):
        with pytest.raises(ValueError, match=f"{env}=true is single-"):
            TPUEngine(cfg, {}, ByteTokenizer(), num_slots=2, max_len=256,
                      mesh=mesh, **kw)


# ------------------------------------------------- traced kernel choice

def test_quantized_matmul_records_the_path_it_took():
    from fasttalk_tpu.ops import quant

    def leaf(k, n):
        w = jax.random.normal(jax.random.PRNGKey(k + n), (k, n))
        q, s = quant.quantize_math_out(w)
        return {"q": q, "s": s}

    x = jnp.ones((3, 1, 256), jnp.float32)
    quant.matmul(x, leaf(256, 384), pallas_ok=True)
    assert quant.traced_paths()["int8 256x384 m=3"] == "pallas"
    quant.matmul(x, leaf(256, 384), pallas_ok=False)
    assert quant.traced_paths()["int8 256x384 m=3"] == "xla:flag_off"
    # K=96 has no >=128-row block: the flag is on, the kernel is not
    # eligible, and that is now visible instead of silent.
    quant.matmul(jnp.ones((3, 1, 96)), leaf(96, 128), pallas_ok=True)
    assert quant.traced_paths()["int8 96x128 m=3"] == \
        "xla:unsupported_shape"
    # T>1 blocks (prefill, spec verify) take XLA by design: not recorded.
    before = quant.traced_paths()
    quant.matmul(jnp.ones((3, 4, 256)), leaf(256, 384), pallas_ok=True)
    assert quant.traced_paths() == before


def test_model_info_reports_kernels_and_the_engine_device():
    from fasttalk_tpu.engine.engine import TPUEngine
    from fasttalk_tpu.engine.tokenizer import ByteTokenizer
    from fasttalk_tpu.models import init_params
    from fasttalk_tpu.models.configs import get_model_config

    cfg = get_model_config("test-tiny")
    eng = TPUEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                    ByteTokenizer(), num_slots=2, max_len=256)
    info = eng.get_model_info()
    assert info["attention_kernel"] == "xla_dense"
    assert isinstance(info["quant_kernels"], dict)
    # One device: the engine's, not every (virtual) device of the host.
    assert len(jax.devices()) == 8
    assert info["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert len(info["devices"]) == 1
    eng.shutdown()


# --------------------------------------------------------- HBM on a TPU

def test_missing_hbm_limit_is_an_error_on_tpu(monkeypatch):
    from fasttalk_tpu.engine.factory import check_hbm_budget
    from fasttalk_tpu.models.configs import get_model_config
    from fasttalk_tpu.utils.config import Config

    class FakeDev:
        def memory_stats(self):
            return {}

    monkeypatch.setattr(jax, "local_devices", lambda: [FakeDev()])
    cfg = Config(llm_provider="tpu", model_name="test-tiny")
    tiny = get_model_config("test-tiny")
    # CPU: no stats, check skipped.
    assert check_hbm_budget(tiny, cfg, jnp.bfloat16, 1)[
        "hbm_limit_bytes"] is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="bytes_limit"):
        check_hbm_budget(tiny, cfg, jnp.bfloat16, 1)
