"""The general generator: device data from ``--seed``, a configuration's
value family (``config["values"]["family"]``, generated by
``values/<family>.py``) and a traffic mix's sizes.

A family module gives ``stream(config, mix, seed)``, the cell's page
stream as (n_pages, page_words) int32 words made on the device in one
jitted call, and ``calibration(config)``, the words the configuration's
base table is fitted on.  A family that serves the ``decode`` kind also
gives ``decode_inputs(config, mix, seed)``.
"""
from __future__ import annotations

from types import ModuleType

import jax
import jax.numpy as jnp
import numpy as np

import byname


def key_of(seed: int, stream: int) -> jax.Array:
    """A PRNG key from a seed of any size (more than 32 bits too)."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, stream)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


def words16(x: jax.Array) -> jax.Array:
    """bf16 -> its bit patterns as int32 0..65535."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.bfloat16), jnp.uint16).astype(jnp.int32)


def bf16_of(w: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(w.astype(jnp.uint16), jnp.bfloat16)


def family(config: dict) -> ModuleType:
    return byname.load("values", config["values"]["family"])


def stream(config: dict, mix: dict, seed: int) -> jax.Array:
    """The cell's page stream: ``mix["stream_bytes"]`` raw bytes of the
    configuration's words, as (n_pages, page_words) int32."""
    return family(config).stream(config, mix, seed)
