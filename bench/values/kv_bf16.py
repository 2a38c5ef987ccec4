"""Value family ``kv_bf16``: attention K/V of one layer in bf16.

Per-channel means plus small per-token noise (the structure of the repo's
``ml_kvcache_bf16`` family), at the configuration's KV geometry, with RoPE
applied to K (and q) at each token's position, as a serving cache holds
them.  The channel means are the layer's own: they come from the
configuration's ``calibration_seed``, like weights, and so does the base
table fitted on them; ``--seed`` makes the tokens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import gen


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate-half RoPE: x (..., T, H, d) f32, pos (T,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def kv_means(key: jax.Array, n_kv: int, hd: int, std: float) -> tuple[jax.Array, jax.Array]:
    km, vm = jax.random.split(key)
    return (std * jax.random.normal(km, (n_kv, hd), jnp.float32),
            std * jax.random.normal(vm, (n_kv, hd), jnp.float32))


def kv_rows(key: jax.Array, means: tuple[jax.Array, jax.Array], lead: tuple[int, ...],
            pos: jax.Array, noise: float, theta: float) -> tuple[jax.Array, jax.Array]:
    """K, V of shape (*lead, T, n_kv, hd) in bf16 for positions ``pos`` (T,)."""
    kn, vn = jax.random.split(key)
    mk, mv = means
    shape = (*lead, pos.shape[0], *mk.shape)
    k = mk + noise * jax.random.normal(kn, shape, jnp.float32)
    v = mv + noise * jax.random.normal(vn, shape, jnp.float32)
    return rope(k, pos, theta).astype(jnp.bfloat16), v.astype(jnp.bfloat16)


def _layer(config: dict) -> tuple[tuple, tuple, jax.Array]:
    kv, vals = config["kv"], config["values"]
    geo = (kv["n_kv"], kv["head_dim"], float(kv["rope_theta"]), kv["positions"])
    return geo, (vals["channel_mean_std"], vals["noise_std"]), \
        gen.key_of(vals["calibration_seed"], 0)


@functools.partial(jax.jit, static_argnames=("n_pages", "page_words", "geo", "values"))
def _stream(mkey, rkey, *, n_pages: int, page_words: int, geo: tuple,
            values: tuple) -> jax.Array:
    n_kv, hd, theta, positions = geo
    mean_std, noise = values
    row = n_kv * hd
    n_tok = n_pages * page_words // (2 * row)
    pos = jnp.arange(n_tok, dtype=jnp.int32) % positions
    k, v = kv_rows(rkey, kv_means(mkey, n_kv, hd, mean_std), (), pos, noise, theta)
    words = jnp.stack([gen.words16(k).reshape(n_tok, row), gen.words16(v).reshape(n_tok, row)],
                      1)
    return words.reshape(n_pages, page_words)


def stream(config: dict, mix: dict, seed: int) -> jax.Array:
    """``mix["stream_bytes"]`` of token rows, each token's K row then its V
    row, as (n_pages, page_words) int32 words; positions cycle over the
    configuration's context."""
    f = config["format"]
    n_pages = mix["stream_bytes"] // (f["page_words"] * f["word_bits"] // 8)
    geo, values, mkey = _layer(config)
    return _stream(mkey, gen.key_of(seed, 0), n_pages=n_pages, page_words=f["page_words"],
                   geo=geo, values=values)


@functools.partial(jax.jit, static_argnames=("tokens", "geo", "values"))
def _calibration(mkey, rkey, *, tokens: int, geo: tuple, values: tuple) -> jax.Array:
    n_kv, hd, theta, positions = geo
    mean_std, noise = values
    pos = jnp.linspace(0, positions - 1, tokens).astype(jnp.int32)
    k, v = kv_rows(rkey, kv_means(mkey, n_kv, hd, mean_std), (), pos, noise, theta)
    return jnp.concatenate([gen.words16(k).reshape(-1), gen.words16(v).reshape(-1)])


def calibration(config: dict) -> jax.Array:
    """The words the layer's base table is fitted on: K and V rows of
    ``calibration_tokens`` tokens spread over the context, from the
    configuration's ``calibration_seed`` alone."""
    geo, values, mkey = _layer(config)
    vals = config["values"]
    return _calibration(mkey, gen.key_of(vals["calibration_seed"], 1),
                        tokens=vals["calibration_tokens"], geo=geo, values=values)


@functools.partial(jax.jit, static_argnames=("batch", "context", "answer", "geo", "heads",
                                             "values"))
def _decode_traffic(mkey, key, *, batch: int, context: int, answer: int, geo: tuple,
                    heads: int, values: tuple) -> dict[str, jax.Array]:
    n_kv, hd, theta, _ = geo
    mean_std, noise = values
    ckey, skey, qkey = jax.random.split(key, 3)
    means = kv_means(mkey, n_kv, hd, mean_std)
    k_ctx, v_ctx = kv_rows(ckey, means, (batch,), jnp.arange(context), noise, theta)
    step_pos = context + jnp.arange(answer)
    k_new, v_new = kv_rows(skey, means, (batch,), step_pos, noise, theta)
    q = rope(jax.random.normal(qkey, (batch, answer, heads, hd), jnp.float32), step_pos, theta)
    # (B, S, ...) -> (S, B, 1, ...): one step's inputs are a leading slice
    steps = [jnp.swapaxes(a, 0, 1)[:, :, None] for a in (q.astype(jnp.bfloat16), k_new, v_new)]
    return {"k_ctx": k_ctx, "v_ctx": v_ctx, "q": steps[0], "k": steps[1], "v": steps[2]}


def decode_inputs(config: dict, mix: dict, seed: int) -> dict[str, jax.Array]:
    """Contexts (B, T0, n_kv, hd) and per-step q (S, B, 1, H, hd), k and v
    (S, B, 1, n_kv, hd), all bf16: distinct sequences over the layer's
    channel means; q and K rotated at their positions."""
    geo, values, mkey = _layer(config)
    return _decode_traffic(mkey, gen.key_of(seed, 1), batch=mix["batch"],
                           context=mix["context"], answer=mix["answer"], geo=geo,
                           heads=config["num_attention_heads"], values=values)
