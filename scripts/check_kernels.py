#!/usr/bin/env python3
"""Compile and run every Pallas entry point once at a model's widths.

For each kernel the engine can route a decode step through, build
operands at the shapes that model gives it, run the kernel (compiled
on a TPU, interpreted on the CPU — ops/pallas_backend.py decides, this
script never overrides it), and compare with the XLA path the engine
takes when the kernel's flag is off:

  int8_matmul     the seven layer leaves (4 distinct K x N), M = slots, 1
  int8_matmul_t   the tied head [V, D], M = slots, 1
  int4_matmul     the same layer shapes at WEIGHT_QUANT_GROUP
  decode_attend   {bf16, int8-KV token, int8-KV head} x T in {1, 8}
  decode_attend_paged   the same over a shuffled block pool

The matmul cases go through ``ops.quant.matmul`` with the flag on, so
they also check that supports*() picks the kernel for these shapes
(``ops.quant.traced_paths``). A case passes when the largest absolute
difference is at most 2% of the reference's largest magnitude: the two
paths round to bf16 at different points, which is the same latitude
the CPU parity tests give bf16 operands (rtol 2e-2).

Prints one line per case, then one JSON object on the last line:
  {"ok": bool, "device": {...}, "interpret": bool, "cases": [...]}
Exit 0 when every case passed, 1 otherwise. Every case runs even after
a failure, so one chip call yields the whole table.

  python scripts/check_kernels.py [--model llama3.2:1b] [--slots 16]
      [--kv-len 1024] [--block-size 16] [--group 128]
CPU debugging: ``JAX_PLATFORMS=cpu ... --model test-small --slots 4
--kv-len 256`` (test-tiny's 64-wide matmuls are below the kernels'
128-row floor and report ``xla:unsupported_shape``).
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from fasttalk_tpu.models.configs import get_model_config  # noqa: E402
from fasttalk_tpu.ops import quant  # noqa: E402
from fasttalk_tpu.ops.attention import attend  # noqa: E402
from fasttalk_tpu.ops.kv_quant import kv_dequantize, kv_quantize  # noqa: E402
from fasttalk_tpu.ops.pallas_attention import (decode_attend,  # noqa: E402
                                               decode_attend_paged)
from fasttalk_tpu.ops.pallas_backend import resolve_interpret  # noqa: E402
from fasttalk_tpu.quantization.int4 import quantize_group  # noqa: E402

TOL = 2e-2


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        raise AssertionError(f"shape {got.shape} != {ref.shape}")
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values in kernel output")
    return float(np.max(np.abs(got - ref))
                 / max(float(np.max(np.abs(ref))), 1e-6))


def _matmul_cases(m, slots: int, group: int):
    """(name, thunk, traced-path key) per matmul case. A thunk runs
    the flag-on call and returns (its result, a thunk for the flag-off
    reference): ``run`` reads the traced path in between, because the
    reference call records under the same key and would overwrite it."""
    dt = jnp.bfloat16
    shapes = sorted({(m.hidden_size, m.q_dim), (m.hidden_size, m.kv_dim),
                     (m.hidden_size, m.intermediate_size),
                     (m.intermediate_size, m.hidden_size),
                     (m.q_dim, m.hidden_size)})
    key = jax.random.PRNGKey(0)
    for k, n in shapes:
        w = jax.random.normal(jax.random.fold_in(key, k * 7 + n),
                              (k, n), jnp.float32) * 0.02
        for rows in (slots, 1):
            x = jax.random.normal(jax.random.fold_in(key, rows),
                                  (rows, 1, k), dt)

            def int8(x=x, w=w):
                q, s = quant.quantize_math_out(w)
                leaf = {"q": q, "s": s}
                return (quant.matmul(x, leaf, pallas_ok=True),
                        lambda: quant.matmul(x, leaf, pallas_ok=False))

            def int4(x=x, w=w):
                leaf = quantize_group(w, group)
                return (quant.matmul(x, leaf, pallas_int4=True),
                        lambda: quant.matmul(x, leaf, pallas_int4=False))

            yield (f"int8_matmul {k}x{n} m={rows}", int8,
                   f"int8 {k}x{n} m={rows}")
            yield (f"int4_matmul g={group} {k}x{n} m={rows}", int4,
                   f"int4 {k}x{n} m={rows}")
    emb = jax.random.normal(jax.random.fold_in(key, 99),
                            (m.vocab_size, m.hidden_size),
                            jnp.float32) * 0.02
    for rows in (slots, 1):
        x = jax.random.normal(jax.random.fold_in(key, 100 + rows),
                              (rows, 1, m.hidden_size), dt)

        def head(x=x):
            q, s = quant.quantize_math_row(emb)
            leaf = {"q": q, "s": s}
            return (quant.matmul_tied(x, leaf, pallas_ok=True),
                    lambda: quant.matmul_tied(x, leaf, pallas_ok=False))

        yield (f"int8_matmul_t {m.vocab_size}x{m.hidden_size} m={rows}",
               head, f"int8_t {m.vocab_size}x{m.hidden_size} m={rows}")


def _lengths(b: int, t: int, s: int) -> jnp.ndarray:
    """Valid-key counts that straddle block edges and hit both ends."""
    picks = [t, 127, 128, 129, s // 2 + 1, s - 1, s]
    return jnp.asarray([min(max(picks[i % len(picks)], t), s)
                        for i in range(b)], jnp.int32)


def _attention_cases(m, slots: int, kv_len: int, block_size: int):
    dt = jnp.bfloat16
    nq, nkv, d = m.num_heads, m.num_kv_heads, m.head_dim
    key = jax.random.PRNGKey(1)
    nb = kv_len // block_size
    pool_blocks = slots * nb + 8
    perm = np.random.default_rng(0).permutation(pool_blocks)[:slots * nb]
    tables = jnp.asarray(perm.reshape(slots, nb).astype(np.int32))
    flat = (np.asarray(tables)[:, :, None] * block_size
            + np.arange(block_size)[None, None, :]).reshape(slots, kv_len)
    for tier in ("bf16", "int8-token", "int8-head"):
        g = {"bf16": 0, "int8-token": 1, "int8-head": nkv}[tier]
        for t in (1, 8):
            kq, kk, kv = jax.random.split(jax.random.fold_in(key, t), 3)
            q = jax.random.normal(kq, (slots, t, nq, d), dt)
            lengths = _lengths(slots, t, kv_len)
            pos = lengths[:, None] - t + jnp.arange(t)[None, :]

            def dense(q=q, kk=kk, kv=kv, lengths=lengths, pos=pos, g=g):
                k = jax.random.normal(kk, (slots, kv_len, nkv, d), dt)
                v = jax.random.normal(kv, (slots, kv_len, nkv, d), dt)
                if not g:
                    return (decode_attend(q, k, v, lengths),
                            attend(q, k, v, pos))
                qk, sk = kv_quantize(k, g)
                qv, sv = kv_quantize(v, g)
                return (decode_attend(q, qk, qv, lengths,
                                      k_scale=sk, v_scale=sv),
                        attend(q, kv_dequantize(qk, sk, dt),
                               kv_dequantize(qv, sv, dt), pos))

            def paged(q=q, kk=kk, kv=kv, lengths=lengths, pos=pos, g=g):
                rows = pool_blocks * block_size
                k = jax.random.normal(kk, (rows, nkv, d), dt)
                v = jax.random.normal(kv, (rows, nkv, d), dt)
                if not g:
                    return (decode_attend_paged(
                                q, k, v, lengths, tables,
                                block_size=block_size),
                            attend(q, k[flat], v[flat], pos))
                qk, sk = kv_quantize(k, g)
                qv, sv = kv_quantize(v, g)
                return (decode_attend_paged(
                            q, qk, qv, lengths, tables,
                            block_size=block_size, k_scale=sk,
                            v_scale=sv),
                        attend(q, kv_dequantize(qk[flat], sk[flat], dt),
                               kv_dequantize(qv[flat], sv[flat], dt),
                               pos))

            yield (f"decode_attend {tier} T={t} S={kv_len}", dense, None)
            yield (f"decode_attend_paged {tier} T={t} bs={block_size} "
                   f"S={kv_len}", paged, None)


def run(model: str, slots: int, kv_len: int, block_size: int,
        group: int) -> dict:
    m = get_model_config(model)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    interpret = resolve_interpret()
    print(f"check_kernels: model={m.name} device={device} "
          f"interpret={interpret}", flush=True)
    cases = []
    for name, thunk, path_key in (
            *_matmul_cases(m, slots, group),
            *_attention_cases(m, slots, kv_len, block_size)):
        case = {"name": name, "ok": False}
        try:
            got, ref = thunk()
            jax.block_until_ready(got)
            if path_key is not None:
                case["path"] = quant.traced_paths().get(path_key)
                ref = ref()
            case["rel_err"] = round(_rel_err(got, ref), 5)
            case["ok"] = (case["rel_err"] <= TOL
                          and case.get("path", "pallas") == "pallas")
        except Exception as e:  # one verdict per kernel, keep going
            lines = traceback.format_exception_only(type(e), e)
            case["error"] = "".join(lines).strip()[-1500:]
        cases.append(case)
        print(f"  {'PASS' if case['ok'] else 'FAIL'} {name} "
              + " ".join(f"{k}={v}" for k, v in case.items()
                         if k not in ("name", "ok")), flush=True)
    return {"ok": all(c["ok"] for c in cases), "device": device,
            "interpret": interpret, "model": m.name, "cases": cases}


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--model", default="llama3.2:1b")
    p.add_argument("--slots", type=int, default=16)
    p.add_argument("--kv-len", type=int, default=1024)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--group", type=int, default=128)
    a = p.parse_args(argv)
    out = run(a.model, a.slots, a.kv_len, a.block_size, a.group)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
