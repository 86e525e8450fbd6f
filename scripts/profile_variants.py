"""In-program decode cost attribution on the real chip.

One jitted K-step decode program per variant; per-piece cost =
difference of MARGINAL per-step time (steps 16 vs 48) between a variant
and the base. Marginal timing cancels the dispatch+fetch round trip and
all per-call fixed cost; swapping one piece per variant attributes the
remainder. (One-op micro-benches time eager-dispatch overhead the real
engine never pays.)

Usage: python scripts/profile_variants.py [variant ...]
Variants: bf16 base mmxla headxla attnpallas greedy nohead
"""

from __future__ import annotations

import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from fasttalk_tpu.models.configs import get_model_config
from fasttalk_tpu.models.llama import KVCache, forward, init_cache
from fasttalk_tpu.models.loader import init_params_device
from fasttalk_tpu.ops.quant import (embed_lookup, matmul_tied,
                                    quantize_params)
from fasttalk_tpu.ops.quant import matmul as qmm
from fasttalk_tpu.ops import rope as rope_mod
from fasttalk_tpu.ops.attention import attend
from fasttalk_tpu.ops.sampling import sample_tokens
from fasttalk_tpu.models.llama import rms_norm, _write_kv
from fasttalk_tpu.utils.compile_cache import enable_compilation_cache

SLOTS = 16
KV_LEN = 512
REPS = 8


def step_fn(params, cfg, cur, pos, active, temps, topks, topps, key,
            sk, sv, *, mm_pallas, head_pallas, attn_pallas, sampling,
            use_head):
    """One decode step, pieces selectable."""
    b = SLOTS
    inv_freq = jnp.asarray(rope_mod.rope_frequencies(
        cfg.head_dim, cfg.rope_theta, cfg.rope_scaling))
    tokens = cur[:, None]
    positions = pos[:, None]
    x = embed_lookup(params["embed"], tokens, params["final_norm"].dtype)
    act = jnp.logical_and(active, pos < KV_LEN)

    def layer(x, scanned):
        lp, ck, cv = scanned
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q, k, v = (qmm(h, lp["wq"], mm_pallas), qmm(h, lp["wk"], mm_pallas),
                   qmm(h, lp["wv"], mm_pallas))
        q = q.reshape(b, 1, cfg.num_heads, cfg.head_dim)
        k = k.reshape(b, 1, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(b, 1, cfg.num_kv_heads, cfg.head_dim)
        q = rope_mod.apply_rope(q, positions, inv_freq)
        k = rope_mod.apply_rope(k, positions, inv_freq)
        ck = _write_kv(ck, k, pos, act)
        cv = _write_kv(cv, v, pos, act)
        if attn_pallas:
            from fasttalk_tpu.ops.pallas_attention import decode_attend

            o = decode_attend(q[:, 0], ck, cv, positions[:, 0] + 1)[:, None]
        else:
            o = attend(q, ck, cv, positions)
        x = x + qmm(o.reshape(b, 1, cfg.q_dim), lp["wo"], mm_pallas)
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        gate = jax.nn.silu(qmm(h, lp["w_gate"], mm_pallas).astype(jnp.float32))
        up = qmm(h, lp["w_up"], mm_pallas).astype(jnp.float32)
        x = x + qmm((gate * up).astype(x.dtype), lp["w_down"], mm_pallas)
        return x, (ck, cv)

    x, (sk, sv) = jax.lax.scan(layer, x, (params["layers"], sk, sv))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if use_head:
        logits = matmul_tied(x, params["embed"], head_pallas)
        lg = logits[:, -1]
        if sampling == "greedy":
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            nxt = sample_tokens(lg, sub, temps, topks, topps,
                                method=sampling)
    else:
        nxt = (cur + x[:, 0, 0].astype(jnp.int32) % 7) % 1000
    return nxt, key, sk, sv


def make_call(cfg, steps, **kw):
    @partial(jax.jit, donate_argnums=(1,), static_argnames=())
    def call(params, cache, cur, pos, active, temps, topks, topps, rng):
        sk = jax.lax.slice_in_dim(cache.k, 0, KV_LEN, axis=2)
        sv = jax.lax.slice_in_dim(cache.v, 0, KV_LEN, axis=2)

        def body(carry, _):
            sk, sv, cur, pos, key = carry
            nxt, key, sk, sv = step_fn(params, cfg, cur, pos, active,
                                       temps, topks, topps, key, sk, sv,
                                       **kw)
            act = jnp.logical_and(active, pos < KV_LEN)
            pos = pos + act.astype(pos.dtype)
            return (sk, sv, nxt, pos, key), nxt

        (sk, sv, cur, pos, rng), toks = jax.lax.scan(
            body, (sk, sv, cur, pos, rng), None, length=steps)
        nk = jax.lax.dynamic_update_slice_in_dim(cache.k, sk, 0, axis=2)
        nv = jax.lax.dynamic_update_slice_in_dim(cache.v, sv, 0, axis=2)
        return KVCache(nk, nv), toks

    return call


VARIANTS = {
    "bf16": dict(mm_pallas=False, head_pallas=False, attn_pallas=False,
                 sampling="fast", use_head=True, quant=False),
    "base": dict(mm_pallas=True, head_pallas=True, attn_pallas=False,
                 sampling="fast", use_head=True, quant=True),
    "mmxla": dict(mm_pallas=False, head_pallas=True, attn_pallas=False,
                  sampling="fast", use_head=True, quant=True),
    "headxla": dict(mm_pallas=True, head_pallas=False, attn_pallas=False,
                    sampling="fast", use_head=True, quant=True),
    "attnpallas": dict(mm_pallas=True, head_pallas=True, attn_pallas=True,
                       sampling="fast", use_head=True, quant=True),
    "greedy": dict(mm_pallas=True, head_pallas=True, attn_pallas=False,
                   sampling="greedy", use_head=True, quant=True),
    "nohead": dict(mm_pallas=True, head_pallas=True, attn_pallas=False,
                   sampling="greedy", use_head=False, quant=True),
}


def main():
    names = sys.argv[1:] or list(VARIANTS)
    enable_compilation_cache()
    cfg = get_model_config("llama3.2:1b")
    print(f"devices: {jax.devices()}", flush=True)
    params_bf16 = init_params_device(cfg, jnp.bfloat16)
    jax.block_until_ready(params_bf16)
    qparams = None

    for name in names:
        kw = dict(VARIANTS[name])
        quant = kw.pop("quant")
        if quant and qparams is None:
            qparams = quantize_params(
                jax.tree.map(lambda x: x, params_bf16))
            jax.block_until_ready(jax.tree.leaves(qparams))
        params = qparams if quant else params_bf16
        res = {}
        for steps in (16, 48):
            cache = init_cache(cfg, SLOTS, 2048, jnp.bfloat16)
            cur = jnp.zeros((SLOTS,), jnp.int32)
            pos = jnp.full((SLOTS,), 100, jnp.int32)
            active = jnp.ones((SLOTS,), bool)
            temps = jnp.full((SLOTS,), 0.7, jnp.float32)
            topks = jnp.full((SLOTS,), 40, jnp.int32)
            topps = jnp.full((SLOTS,), 0.9, jnp.float32)
            rng = jax.random.PRNGKey(0)
            fn = make_call(cfg, steps, **kw)
            cache, toks = fn(params, cache, cur, pos, active, temps,
                             topks, topps, rng)
            np.asarray(toks)
            cur = jnp.asarray(np.asarray(toks[-1]) % cfg.vocab_size)
            t0 = time.perf_counter()
            for _ in range(REPS):
                cache, toks = fn(params, cache, cur, pos, active, temps,
                                 topks, topps, rng)
                cur = toks[-1] % cfg.vocab_size
            np.asarray(toks)
            res[steps] = (time.perf_counter() - t0) / REPS
            del cache
        marg = (res[48] - res[16]) / 32
        print(f"{name:12s}: marginal {marg * 1e3:6.2f} ms/step "
              f"(16: {res[16] * 1e3:6.1f}  48: {res[48] * 1e3:6.1f} ms/call)",
              flush=True)


if __name__ == "__main__":
    main()
