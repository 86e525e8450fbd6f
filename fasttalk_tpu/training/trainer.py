"""Tiny-model training loop on top of the sharded training stack.

Reuses parallel/train.py's ``causal_lm_loss`` (the same forward pass the
engine serves) and optax, with one addition: the packed dataset lives
ON the device and each step gathers its batch in-program from a
folded-in PRNG key, so a run ships ~12 MB of tokens through the host
link once instead of ~66 KB × 5,000 as per-call arguments.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fasttalk_tpu.models.configs import ModelConfig
from fasttalk_tpu.models.llama import KVCache, forward
from fasttalk_tpu.parallel.train import causal_lm_loss


def pack_tokens(token_stream: list[int], seq_len: int) -> np.ndarray:
    """Pack a flat token stream into [N, seq_len + 1] rows (the +1
    feeds next-token targets). The tail remainder is dropped."""
    row = seq_len + 1
    n = len(token_stream) // row
    return np.asarray(token_stream[:n * row], np.int32).reshape(n, row)


def make_sampled_train_step(cfg: ModelConfig,
                            optimizer: optax.GradientTransformation,
                            mesh: Mesh, batch: int) -> Callable:
    """``(params, opt_state, data, step) -> (params, opt_state, loss)``
    where ``data`` is the device-resident packed dataset [N, T+1] and
    the batch rows are gathered in-program from a step-derived key
    (sampling with replacement — fine for a many-epoch tiny run)."""
    batch_sharding = NamedSharding(mesh, P("dp", None))

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, data, step):
        key = jax.random.fold_in(jax.random.PRNGKey(17), step)
        idx = jax.random.randint(key, (batch,), 0, data.shape[0])
        tokens = jax.lax.with_sharding_constraint(
            data[idx], batch_sharding)
        loss, grads = jax.value_and_grad(causal_lm_loss)(
            params, cfg, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


def make_eval_loss(cfg: ModelConfig) -> Callable:
    """Jitted mean next-token loss over a fixed [B, T+1] batch."""

    @jax.jit
    def step(params, tokens):
        return causal_lm_loss(params, cfg, tokens)

    return step


@partial(jax.jit, static_argnames=("cfg",))
def _bucketed_next_token(params, cfg: ModelConfig, tokens, last_index):
    """argmax next token for a padded [1, B] prompt whose real length is
    last_index + 1 (causal masking ignores the padding keys)."""
    b = tokens.shape[1]
    positions = jnp.arange(b)[None, :]
    dtype = params["final_norm"].dtype
    cache = KVCache(
        k=jnp.zeros((cfg.num_layers, 1, b, cfg.num_kv_heads,
                     cfg.head_dim), dtype),
        v=jnp.zeros((cfg.num_layers, 1, b, cfg.num_kv_heads,
                     cfg.head_dim), dtype))
    logits, _ = forward(params, cfg, tokens, positions, cache,
                        jnp.zeros((1,), jnp.int32),
                        logits_indices=last_index[None])
    return jnp.argmax(logits[0, -1])


def greedy_generate(params: Any, cfg: ModelConfig, prompt_ids: list[int],
                    max_new: int = 48, eos_id: int | None = None,
                    ) -> list[int]:
    """Host-driven greedy decode for in-training eval (one bucketed
    full-prompt forward per token — slow but dependency-free; serving
    uses the real engine). Prompts pad to 64-token buckets so the jit
    cache stays small across the probe's growing lengths."""
    ids = list(prompt_ids)
    for _ in range(max_new):
        t = len(ids)
        bucket = -(-t // 64) * 64
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :t] = ids
        nxt = int(_bucketed_next_token(params, cfg, padded,
                                       jnp.int32(t - 1)))
        ids.append(nxt)
        if eos_id is not None and nxt == eos_id:
            break
    return ids[len(prompt_ids):]


def single_device_mesh() -> Mesh:
    """A ("dp", "sp", "tp") mesh over one device — the degenerate shape
    that lets the sharded train step run anywhere."""
    dev = np.asarray(jax.devices()[:1]).reshape(1, 1, 1)
    return Mesh(dev, ("dp", "sp", "tp"))


def train_tokenizer(texts: list[str], vocab_size: int, specials: list[str],
                    out_path: str) -> Any:
    """Train a ByteLevel BPE on the corpus (same recipe as
    scripts/make_bench_tokenizer.py) with the chat specials."""
    from tokenizers import Tokenizer, decoders, pre_tokenizers, processors
    from tokenizers.models import BPE
    from tokenizers.trainers import BpeTrainer

    tok = Tokenizer(BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    tok.post_processor = processors.ByteLevel(trim_offsets=False)
    trainer = BpeTrainer(vocab_size=vocab_size, special_tokens=specials,
                         show_progress=False)
    tok.train_from_iterator(texts, trainer)
    tok.save(out_path)
    return tok
