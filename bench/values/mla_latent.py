"""Value family ``mla_latent``: hidden states entering a stack of
multi-head latent attention layers, in bf16.

Each layer has per-channel means of its own, made from the
configuration's ``calibration_seed`` like weights; ``--seed`` makes the
per-token noise.  The cache's latent rows are the program's projection of
these states, so their values follow from the weights and the states.
Every function is jitted once per shape: the same seed and arguments give
the same bits in set-up and in the check.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import gen


def _layers(config: dict) -> tuple[int, int, float, float, int]:
    v = config["values"]
    return (config["num_hidden_layers"], config["hidden_size"], float(v["hidden_mean_std"]),
            float(v["hidden_noise_std"]), v["calibration_seed"])


@functools.partial(jax.jit, static_argnames=("layers", "hidden", "mean_std"))
def _means(key, *, layers: int, hidden: int, mean_std: float) -> jax.Array:
    return mean_std * jax.random.normal(key, (layers, 1, 1, hidden), jnp.float32)


@functools.partial(jax.jit, static_argnames=("shape", "noise"))
def _states(means, key, *, shape: tuple, noise: float) -> jax.Array:
    """(L, *shape[1:]) bf16: each layer's means plus token noise."""
    x = means.reshape(means.shape[0], *(1,) * (len(shape) - 2), means.shape[-1]) \
        + noise * jax.random.normal(key, shape, jnp.float32)
    return x.astype(jnp.bfloat16)


def means(config: dict) -> jax.Array:
    L, d, mean_std, _, cal = _layers(config)
    return _means(gen.key_of(cal, 0), layers=L, hidden=d, mean_std=mean_std)


def context(config: dict, seed: int, chunk: int, batch: int, tokens: int) -> jax.Array:
    """Chunk ``chunk`` of every layer's context, (L, B, tokens, hidden)."""
    L, d, _, noise, _ = _layers(config)
    key = jax.random.fold_in(gen.key_of(seed, 1), chunk)
    return _states(means(config), key, shape=(L, batch, tokens, d), noise=noise)


def steps(config: dict, seed: int, batch: int, answer: int) -> jax.Array:
    """Every answer step's states, (S, L, B, 1, hidden): one step's input is
    a leading slice."""
    L, d, _, noise, _ = _layers(config)
    x = _states(means(config), gen.key_of(seed, 2), shape=(L, answer, batch, d), noise=noise)
    return jnp.transpose(x, (1, 0, 2, 3))[:, :, :, None]


def calibration(config: dict) -> jax.Array:
    """The states the layers' base tables are fitted on, (L, 1, tokens,
    hidden), from the configuration's ``calibration_seed`` alone."""
    L, d, _, noise, cal = _layers(config)
    tokens = config["values"]["calibration_tokens"]
    return _states(means(config), gen.key_of(cal, 1), shape=(L, 1, tokens, d), noise=noise)
