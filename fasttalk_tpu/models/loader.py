"""Weight loading: HF safetensors checkpoints → stacked JAX pytrees.

The reference never loads weights in-tree — its external engines pull
them into docker volumes (SURVEY.md §5 checkpoint/resume: none in-tree;
config MODEL_PATH existed at reference config.py:157 but nothing read
it). Here MODEL_PATH points at a HF-format checkpoint directory and the
loader builds the stacked-layer pytree the scan-based forward expects,
optionally placing shards straight onto a device mesh.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from fasttalk_tpu.models.configs import ModelConfig
from fasttalk_tpu.models.llama import Params, init_params
from fasttalk_tpu.utils.logger import get_logger

log = get_logger("models.loader")

# HF parameter name templates → (our pytree path, needs_transpose).
# HF Linear stores [out, in]; our forward uses x @ w so we keep [in, out].
_LAYER_MAP = {
    "model.layers.{i}.input_layernorm.weight": ("attn_norm", False),
    "model.layers.{i}.self_attn.q_proj.weight": ("wq", True),
    "model.layers.{i}.self_attn.k_proj.weight": ("wk", True),
    "model.layers.{i}.self_attn.v_proj.weight": ("wv", True),
    "model.layers.{i}.self_attn.o_proj.weight": ("wo", True),
    "model.layers.{i}.post_attention_layernorm.weight": ("mlp_norm", False),
    "model.layers.{i}.mlp.gate_proj.weight": ("w_gate", True),
    "model.layers.{i}.mlp.up_proj.weight": ("w_up", True),
    "model.layers.{i}.mlp.down_proj.weight": ("w_down", True),
}
# Qwen2-style attention biases, present only when cfg.qkv_bias.
_BIAS_MAP = {
    "model.layers.{i}.self_attn.q_proj.bias": ("bq", False),
    "model.layers.{i}.self_attn.k_proj.bias": ("bk", False),
    "model.layers.{i}.self_attn.v_proj.bias": ("bv", False),
}


def find_checkpoint_dir(model_path: str, model_name: str) -> str | None:
    """Locate a safetensors checkpoint under MODEL_PATH for model_name."""
    candidates = [
        model_path,
        os.path.join(model_path, model_name.replace(":", "_")),
        os.path.join(model_path, model_name.replace(":", "-")),
        # HF-style org/name: flattened (scripts/fetch_model.py layout)
        # or nested as-is.
        os.path.join(model_path,
                     model_name.replace(":", "_").replace("/", "_")),
        os.path.join(model_path, model_name),
    ]
    for c in candidates:
        if os.path.isdir(c) and any(f.endswith(".safetensors")
                                    for f in os.listdir(c)):
            return c
    return None


def _open_all_tensors(ckpt_dir: str) -> dict[str, Any]:
    """Map tensor name → (file handle accessor). Supports sharded index."""
    from safetensors import safe_open

    files = sorted(f for f in os.listdir(ckpt_dir) if f.endswith(".safetensors"))
    index_path = os.path.join(ckpt_dir, "model.safetensors.index.json")
    name_to_file: dict[str, str] = {}
    if os.path.exists(index_path):
        with open(index_path) as f:
            name_to_file = json.load(f)["weight_map"]
    else:
        for fname in files:
            with safe_open(os.path.join(ckpt_dir, fname), framework="pt") as sf:
                for key in sf.keys():
                    name_to_file[key] = fname
    return name_to_file


def load_params(cfg: ModelConfig, ckpt_dir: str,
                dtype: jnp.dtype = jnp.bfloat16,
                put: Callable[[np.ndarray, str], jax.Array] | None = None,
                ) -> Params:
    """Load a HF Llama checkpoint into the stacked pytree.

    ``put(host_array, pytree_path) -> jax.Array`` lets the caller place
    each tensor with a sharding (parallel/sharding.py provides one);
    default is plain device_put.
    """
    from safetensors import safe_open

    name_to_file = _open_all_tensors(ckpt_dir)
    handles: dict[str, Any] = {}

    def get(name: str) -> np.ndarray:
        # framework="pt": the numpy framework cannot represent bf16 (raises
        # TypeError), and real HF Llama checkpoints are stored bf16.
        import torch

        fname = name_to_file[name]
        if fname not in handles:
            handles[fname] = safe_open(os.path.join(ckpt_dir, fname),
                                       framework="pt")
        t = handles[fname].get_tensor(name)
        if t.dtype == torch.bfloat16:
            t = t.to(torch.float32)
        return t.numpy()

    if put is None:
        def put(arr: np.ndarray, path: str) -> jax.Array:  # noqa: ARG001
            return jax.device_put(jnp.asarray(arr, dtype))

    def cast(a: np.ndarray) -> np.ndarray:
        return np.asarray(a, np.float32)

    params: Params = {
        "embed": put(cast(get("model.embed_tokens.weight")), "embed"),
        "final_norm": put(cast(get("model.norm.weight")), "final_norm"),
        "layers": {},
    }
    layer_map = dict(_LAYER_MAP)
    if cfg.qkv_bias:
        layer_map.update(_BIAS_MAP)
    for tmpl, (path, transpose) in layer_map.items():
        stacked = []
        for i in range(cfg.num_layers):
            t = cast(get(tmpl.format(i=i)))
            stacked.append(t.T if transpose else t)
        params["layers"][path] = put(np.stack(stacked), f"layers/{path}")
    if not cfg.tie_embeddings:
        params["lm_head"] = put(cast(get("lm_head.weight")).T, "lm_head")
    for h in handles.values():
        h.__exit__(None, None, None)
    log.info(f"Loaded checkpoint from {ckpt_dir}", model=cfg.name)
    return params


def init_params_device(cfg: ModelConfig, dtype: jnp.dtype = jnp.bfloat16,
                       mesh=None, quantize: bool | str = False,
                       seed: int = 0,
                       weight_quant_group: int = 128) -> Params:
    """Architecture-faithful random init generated ON the device(s),
    one jitted program per leaf — zero host->device weight transfer,
    which matters both for multi-chip placement (each leaf materialises
    directly in its TP shards) and for weight-free benchmarking
    (host-initialising an 8B model ships gigabytes over the host link;
    this ships one RNG key). ``quantize``
    int8-quantizes matmul leaves inside the same per-leaf program,
    layer by layer, so the f32 generation buffer never exceeds one
    layer slice (see the peak-memory note below). It also accepts a
    tier string — "none"/"off" | "int8" (== True) | "int4", the
    WEIGHT_QUANT surface; int4 packs the seven layer matmuls group-wise
    (``weight_quant_group``; fasttalk_tpu/quantization/int4.py) while
    the embedding/lm_head keep their int8 per-row formats.
    """
    import zlib

    from fasttalk_tpu.ops.quant import QUANTIZED_LEAVES
    from fasttalk_tpu.quantization.int4 import INT4_LEAVES

    tier = (quantize if isinstance(quantize, str)
            else ("int8" if quantize else "none"))
    tier = {"off": "none", "": "none"}.get(tier, tier)
    weight_quant_group = int(weight_quant_group)

    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(seed), dtype))

    # One jitted program PER LEAF, with layer-stacked leaves filled by a
    # fori_loop writing into a donated accumulator. A single all-leaves
    # program (the previous design) let XLA schedule several leaves'
    # f32 generation buffers live at once — for an 8B model one stacked
    # MLP leaf alone is a 7.5 GB f32 temporary, and the combined peak
    # OOMed a 16 GiB chip before serving ever started. Per-leaf programs
    # bound the peak to (committed leaves so far) + one layer slice;
    # rbg keys keep each compile small, repeated shapes hit the jit
    # cache, and dispatches are async so the host round trip is paid
    # ~once, not per leaf.
    def _gen_leaf(base_key, crc, *, kind, shape, leaf_quantize):
        # leaf_quantize: False | "out" (per-output-channel, matmul
        # weights) | "row" (per-row, the embedding) | "out_t" (the
        # untied lm_head, stored transposed — ops/quant.py
        # _quantize_head_t; same scale math, kernel-streamable layout)
        # | "group" (int4 group-wise + nibble packing, shared math with
        # quantization/int4.py so generated and checkpoint-quantized
        # leaves can never diverge).
        if kind == "ones":
            return jnp.ones(shape, dtype)
        if kind == "zeros":
            return jnp.zeros(shape, dtype)
        key = jax.random.fold_in(base_key, crc)
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        scale = fan_in ** -0.5

        def make_slice(k, sl_shape):
            return jax.random.normal(k, sl_shape, jnp.float32) * scale

        def quantize_f32(wf):
            # Shared math with ops/quant.py so generated and
            # checkpoint-quantized tables are bit-identical.
            from fasttalk_tpu.ops.quant import (quantize_math_out,
                                                quantize_math_row)

            if leaf_quantize == "row":
                return quantize_math_row(wf)
            return quantize_math_out(wf)

        if len(shape) == 3:
            # Layer-stacked: generate one [in, out] f32 slice per layer
            # and write it into the accumulator in place.
            num_layers = shape[0]
            if leaf_quantize == "group":
                from fasttalk_tpu.quantization.int4 import (
                    pack_int4, quantize_math_group)

                def body(layer, acc):
                    accq, accs = acc
                    sl = make_slice(jax.random.fold_in(key, layer),
                                    shape[1:])
                    q, s = quantize_math_group(sl, weight_quant_group)
                    return (accq.at[layer].set(pack_int4(q)),
                            accs.at[layer].set(s))

                accq, accs = jax.lax.fori_loop(
                    0, num_layers, body,
                    (jnp.zeros((shape[0], shape[1] // 2, shape[2]),
                               jnp.uint8),
                     jnp.zeros((shape[0], shape[1] // weight_quant_group,
                                shape[2]), jnp.float32)))
                return {"q4": accq, "s": accs}
            if leaf_quantize:
                def body(layer, acc):
                    accq, accs = acc
                    sl = make_slice(jax.random.fold_in(key, layer),
                                    shape[1:])
                    q, s = quantize_f32(sl)
                    return (accq.at[layer].set(q), accs.at[layer].set(s))

                accq, accs = jax.lax.fori_loop(
                    0, num_layers, body,
                    (jnp.zeros(shape, jnp.int8),
                     jnp.zeros((shape[0], shape[2]), jnp.float32)))
                return {"q": accq, "s": accs}

            def body(layer, acc):
                sl = make_slice(jax.random.fold_in(key, layer), shape[1:])
                return acc.at[layer].set(sl.astype(dtype))

            return jax.lax.fori_loop(0, num_layers, body,
                                     jnp.zeros(shape, dtype))

        wf = make_slice(key, shape)
        if leaf_quantize == "out_t":
            q, s = quantize_f32(wf)  # per-output-channel on [D, V]
            return {"qt": q.T, "s": s}  # identical values, [V, D] layout
        if leaf_quantize:
            q, s = quantize_f32(wf)
            return {"q": q, "s": s}
        return wf.astype(dtype)

    gen_leaf = jax.jit(_gen_leaf,
                       static_argnames=("kind", "shape", "leaf_quantize"))

    # "rbg" (XLA RngBitGenerator), not threefry: threefry over 10^9
    # elements compiles ~4x slower. rbg is also the JAX-recommended impl
    # for sharded generation (no cross-device communication). Weight-
    # free init only feeds tests and benchmarks, so RNG quality is not
    # load-bearing.
    base_key = jax.random.key(seed, impl="rbg")

    # Mesh-path jit wrappers memoized by their output sharding: a fresh
    # jax.jit per leaf would re-trace/re-compile repeated shapes (the
    # seven layer-stacked leaves mostly share them).
    _sharded_fns: dict[Any, Any] = {}

    def _sharded_gen(out_sh):
        key = (tuple(sorted(out_sh.items())) if isinstance(out_sh, dict)
               else out_sh)
        fn = _sharded_fns.get(key)
        if fn is None:
            fn = jax.jit(_gen_leaf,
                         static_argnames=("kind", "shape", "leaf_quantize"),
                         out_shardings=out_sh)
            _sharded_fns[key] = fn
        return fn

    def gen(path, sds):
        name = str(getattr(path[-1], "key", path[-1]))
        shape = sds.shape
        if "norm" in name:
            kind = "ones"
        elif name in ("bq", "bk", "bv"):
            kind = "zeros"
        else:
            kind = "normal"
        leaf_quantize: bool | str = False
        if tier != "none" and kind == "normal":
            if name == "lm_head":
                leaf_quantize = "out_t"
            elif tier == "int4" and name in INT4_LEAVES:
                leaf_quantize = "group"
            elif name in QUANTIZED_LEAVES:
                leaf_quantize = "out"
            elif name == "embed":
                leaf_quantize = "row"
        # crc32, not hash(): Python's hash is salted per process, which
        # would give each host of a multi-host slice different weights
        # for the same leaf (and break same-seed reproducibility).
        full = "/".join(str(getattr(k, "key", k)) for k in path)
        crc = zlib.crc32(full.encode()) & 0x7FFFFFFF
        fn = gen_leaf
        if mesh is not None:
            from jax.sharding import NamedSharding

            from fasttalk_tpu.parallel.sharding import (_parent_name,
                                                        _spec_for)

            if leaf_quantize == "group":
                qshape = shape[:-2] + (shape[-2] // 2, shape[-1])
                s_shape = shape[:-2] + (
                    shape[-2] // weight_quant_group, shape[-1])
                out_sh = {
                    "q4": NamedSharding(mesh, _spec_for(
                        "q4", len(qshape), qshape, parent=name)),
                    "s": NamedSharding(mesh, _spec_for(
                        "s", len(s_shape), s_shape, parent=name)),
                }
            elif leaf_quantize:
                s_shape = (shape[:-1] if leaf_quantize == "row"
                           else shape[:-2] + shape[-1:])
                qname = "qt" if leaf_quantize == "out_t" else "q"
                qshape = (shape[::-1] if leaf_quantize == "out_t"
                          else shape)
                out_sh = {
                    qname: NamedSharding(mesh, _spec_for(
                        qname, len(qshape), qshape, parent=name)),
                    "s": NamedSharding(mesh, _spec_for(
                        "s", len(s_shape), s_shape, parent=name)),
                }
            else:
                out_sh = NamedSharding(
                    mesh, _spec_for(name, len(shape), shape,
                                    parent=_parent_name(path)))
            fn = _sharded_gen(out_sh)
        return fn(base_key, crc, kind=kind, shape=shape,
                  leaf_quantize=leaf_quantize)

    params = jax.tree_util.tree_map_with_path(gen, shapes)
    log.info(f"Random-initialised {cfg.name} on device "
             f"({tier if tier != 'none' else jnp.dtype(dtype).name}"
             f"{', sharded' if mesh is not None else ''})")
    return params


def load_or_init(cfg: ModelConfig, model_path: str,
                 dtype: jnp.dtype = jnp.bfloat16,
                 put: Callable[[np.ndarray, str], jax.Array] | None = None,
                 seed: int = 0, mesh=None,
                 quantize: bool = False) -> tuple[Params, bool]:
    """Load weights if a checkpoint exists under model_path, else random
    init (architecture-faithful; used for tests and weight-free perf work).

    ``put`` applies to the checkpoint-streaming path. The random path
    routes through init_params_device when ``mesh``/``quantize`` is
    given (direct-to-shard, no host->device weight transfer) — a bare
    ``put`` cannot express those semantics, so passing put without a
    checkpoint is rejected rather than silently ignored.

    Returns (params, loaded_from_checkpoint).
    """
    ckpt = find_checkpoint_dir(model_path, cfg.name) if model_path else None
    if ckpt:
        return load_params(cfg, ckpt, dtype, put), True
    log.warning(
        f"No checkpoint for {cfg.name!r} under {model_path!r}; "
        "using random-initialised weights")
    if put is not None:
        raise ValueError(
            "load_or_init: no checkpoint found and `put` cannot drive "
            "random init — pass mesh=/quantize= (routed through "
            "init_params_device) instead")
    if mesh is not None or quantize:
        return init_params_device(cfg, dtype, mesh=mesh,
                                  quantize=quantize, seed=seed), False
    return init_params(cfg, jax.random.PRNGKey(seed), dtype), False
