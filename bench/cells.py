"""What the drivers share: the program's format and table objects built
from a configuration, the configuration's base table, and the driver of
a traffic kind, found by name in ``drivers/<kind>.py``.

A driver is built from a configuration, a mix and a seed, and has
``setup()``; ``window(seconds)``, which returns what the end-to-end
metrics need and the seconds each timed call took (``times``);
``outputs()``, which copies what the check compares to the host and
drops the program's state; ``check(out, limits)``, which runs the
reference; and ``control(out)``, the reference one precision below in
the program's place.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import byname
import gen
import table
from fmt import Format


def driver(config: dict, mix: dict, seed: int):
    return byname.load("drivers", mix["kind"]).Driver(config, mix, seed)


def fr_config(f: Format):
    from repro.core.gbdi_fr import FRConfig

    return FRConfig(word_bits=f.word_bits, page_words=f.page_words, num_bases=f.num_bases,
                    width_set=f.width_set, bucket_caps=f.bucket_caps,
                    outlier_cap=f.outlier_cap)


def base_table(bases: np.ndarray, widths: np.ndarray):
    from repro.core.format import BaseTable

    return BaseTable(jnp.asarray(bases, jnp.int32), jnp.asarray(widths, jnp.int32))


def config_table(config: dict, f: Format) -> tuple[np.ndarray, np.ndarray]:
    """The configuration's base table: the benchmark's fit on its value
    family's calibration words, the same for every seed and cell."""
    return table.fit(np.asarray(gen.family(config).calibration(config)), f)
